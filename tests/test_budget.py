"""Serving-budget ledger tests: trace ingestion, link separation, SLO
verdicts + slo_* gauges, the /debug/budget endpoint, the loopback bench
plumbing (fake encoder — no XLA compile in this module), and the
startup memory gauges (obs/procstats)."""

import asyncio
import json

import pytest
from aiohttp import ClientSession

from docker_nvidia_glx_desktop_tpu.obs import budget as obsb
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
from docker_nvidia_glx_desktop_tpu.web.server import bound_port, serve


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 60))
    finally:
        loop.close()


MARKS = (("capture", 0.000), ("captured", 0.002),
         ("device-submit", 0.006), ("device-collect", 0.016),
         ("bitstream", 0.018), ("publish", 0.0185))


def feed(led, frames=20, marks=MARKS):
    rec = obst.TraceRecorder("feed", capacity=64)
    rec.add_listener(led._on_trace)
    for i in range(frames):
        rec.record_marks(i, marks, pts=i * 1500)
    return rec


class TestLedger:
    def test_marks_become_stage_windows(self):
        led = obsb.BudgetLedger()
        feed(led, frames=5)
        s = led.stage_summary()
        # spans named after the mark they END on (trace contract)
        assert set(s) == {"captured", "device-submit", "device-collect",
                          "bitstream", "publish", "total"}
        assert s["device-collect"]["p50"] == pytest.approx(10.0)
        assert s["total"]["p50"] == pytest.approx(18.5)
        assert led.frames == 5

    def test_span_listener_and_direct_feed(self):
        led = obsb.BudgetLedger()
        rec = obst.TraceRecorder("spans", capacity=8)
        rec.add_listener(led._on_trace)
        rec.record_span("rtp-sent", 0.0, 0.003, 1)
        led.observe_stage("batch-dispatch-mjpeg", 7.5)
        s = led.stage_summary()
        assert s["rtp-sent"]["p50"] == pytest.approx(3.0)
        assert s["batch-dispatch-mjpeg"]["p50"] == pytest.approx(7.5)
        assert led.frames == 0              # spans are not whole frames

    def test_link_separation(self):
        led = obsb.BudgetLedger()
        feed(led)
        assert led.compute_p50_ms() == pytest.approx(18.5)   # unprobed
        led.set_link_rtt(5.0)
        assert led.e2e_p50_ms() == pytest.approx(18.5)
        assert led.compute_p50_ms() == pytest.approx(13.5)

    def test_link_separation_clamps_at_host_stage_floor(self):
        """A noisy probe larger than the whole collect stage must not
        drive the compute view below the sum of the non-link stages."""
        led = obsb.BudgetLedger()
        feed(led)
        led.set_link_rtt(17.0)              # > collect p50 (10 ms)
        floor = 2.0 + 4.0 + 2.0 + 0.5       # captured+submit+bits+publish
        assert led.compute_p50_ms() == pytest.approx(floor)

    def test_floor_ignores_non_frame_spans(self):
        """Free-standing spans (batch dispatch, rtp) are not part of
        the capture->publish path: they must not inflate the clamp
        floor and distort the link-separated compute view."""
        led = obsb.BudgetLedger()
        feed(led)
        for _ in range(10):                 # 30 ms batch spans
            led.observe_stage("batch-dispatch-mjpeg", 30.0)
            led.observe_stage("rtp-sent", 25.0)
        led.set_link_rtt(5.0)
        # e2e 18.5 - link 5 = 13.5, NOT clamped up by the 55 ms of spans
        assert led.compute_p50_ms() == pytest.approx(13.5)

    def test_dispatch_stage_and_summary(self):
        """ISSUE 8 satellite: crossings-per-frame and submit-to-launch
        gap are first-class ledger data — a scraped gauge, not a
        bench-only number."""
        led = obsb.BudgetLedger()
        assert led.dispatch_summary() is None
        # a per-frame path: 1 crossing each; then a chunk of 4 (the
        # dispatch frame carries the chunk's single crossing)
        for _ in range(4):
            led.record_dispatch(1, 2.0)
        for _ in range(3):
            led.record_dispatch(0, 0.0)
        led.record_dispatch(1, 3.0)
        d = led.dispatch_summary()
        assert d["n"] == 8
        assert d["crossings_per_frame"] == pytest.approx(5 / 8)
        assert "dispatch" in led.stage_summary()
        # dispatch is a free-standing span: it must not join the
        # compute-floor clamp's frame stages
        assert "dispatch" not in led._frame_stages
        assert led.evaluate()["dispatch"]["n"] == 8
        led.clear()
        assert led.dispatch_summary() is None

    def test_dispatch_gauges_registered(self):
        fams = obsm.REGISTRY.render()
        assert "dngd_dispatch_crossings_per_frame" in fams
        assert "dngd_dispatch_gap_ms" in fams

    def test_spatial_overhead_stages_and_gauges(self):
        """ISSUE 12 satellite: bitstream-stitch is a first-class ledger
        sub-stage — a 4K regression names the leaking stage instead of a
        blended device number.  (The halo exchange is inside the device
        program: a device trace attributes it, no host clock can.)"""
        led = obsb.BudgetLedger()
        led.record_spatial(stitch_ms=0.4)
        led.record_spatial(stitch_ms=0.6)
        s = led.stage_summary()
        assert s["bitstream-stitch"]["n"] == 2
        assert s["bitstream-stitch"]["p50"] in (0.4, 0.6)
        # a free-standing span: never part of the compute-floor clamp
        assert "bitstream-stitch" not in led._frame_stages
        # the /debug/budget text carries the row
        txt = obsb.render_budget_text(led)
        assert "bitstream-stitch" in txt
        # globally-registered gauges read the default LEDGER
        fams = obsm.REGISTRY.render()
        assert "dngd_stitch_ms" in fams
        assert "dngd_halo_ms" not in fams

    def test_window_is_rolling(self):
        led = obsb.BudgetLedger(window=4)
        rec = feed(led, frames=3)
        slow = (("capture", 0.0), ("publish", 1.0))   # 1000 ms frames
        for i in range(4):
            rec.record_marks(100 + i, slow)
        assert led.stage_summary()["total"]["p50"] == pytest.approx(1000)


class TestSlo:
    def test_active_rung_matches_geometry(self):
        led = obsb.BudgetLedger()
        led.set_context(1920, 1080, 60)
        assert led.active_rung().name == "1080p60"
        led.set_context(640, 480, 25)
        rung = led.active_rung()
        assert rung.name.startswith("custom_")
        assert rung.budget_ms == pytest.approx(40.0)   # frame interval

    def test_multisession_rung_reachable(self):
        """Rung 5 (8x1080p60) is distinguished from rung 3 by the
        session count, not by geometry alone."""
        led = obsb.BudgetLedger()
        led.set_context(1920, 1080, 60, sessions=8)
        assert led.active_rung().name == "8x1080p60"
        led.set_context(1920, 1080, 60, sessions=1)
        assert led.active_rung().name == "1080p60"
        led.set_context(1920, 1080, 60, sessions=4)    # off-ladder
        assert led.active_rung().name == "custom_4x1920x1080@60"

    def test_verdicts_and_attribution(self):
        led = obsb.BudgetLedger()
        led.set_context(1920, 1080, 60)
        ev = led.evaluate()
        assert ev["rungs"]["1080p60"]["ok"] is None    # no data yet
        feed(led)
        led.set_link_rtt(5.0)
        ev = led.evaluate()
        r = ev["rungs"]["1080p60"]
        assert r["active"] and r["ok"] is True
        assert r["p50_ms"] == pytest.approx(13.5)
        assert r["margin_ms"] == pytest.approx(6.5)
        # attribution: stages sorted by p50 descending, share of budget
        att = r["attribution"]
        assert att[0]["stage"] == "device-collect"
        assert att[0]["budget_pct"] == pytest.approx(50.0)
        # a regression names its stage: blow up the bitstream stage
        for _ in range(600):
            led.observe_stage("bitstream", 30.0)
        worst = led.evaluate()["rungs"]["1080p60"]["attribution"][0]
        assert worst["stage"] == "bitstream"

    def test_over_budget_flips_ok(self):
        led = obsb.BudgetLedger()
        led.set_context(1920, 1080, 60)
        slow = (("capture", 0.0), ("publish", 0.050))   # 50 ms e2e
        rec = obst.TraceRecorder("slow", capacity=8)
        rec.add_listener(led._on_trace)
        for i in range(5):
            rec.record_marks(i, slow)
        r = led.evaluate()["rungs"]["1080p60"]
        assert r["ok"] is False and r["margin_ms"] < 0

    def test_slo_gauges_evaluate_1080p60_from_ledger_data(self):
        """Acceptance: /metrics slo_* gauges evaluate the 1080p60
        <= 20 ms rung from the same data the ledger holds."""
        reg = obsm.Registry()
        led = obsb.BudgetLedger()
        obsb.register_slo_gauges(led, reg)
        text = reg.render()
        assert 'slo_ok{rung="1080p60"} -1' in text      # no data yet
        assert 'slo_budget_ms{rung="1080p60"} 20' in text
        led.set_context(1920, 1080, 60)
        feed(led)
        led.set_link_rtt(5.0)
        text = reg.render()
        assert 'slo_ok{rung="1080p60"} 1' in text
        assert 'slo_p50_ms{rung="1080p60"} 13.5' in text
        assert 'slo_e2e_p50_ms{rung="1080p60"} 18.5' in text
        assert 'slo_margin_ms{rung="1080p60"} 6.5' in text
        assert 'slo_active{rung="1080p60"} 1' in text
        assert 'slo_link_rtt_ms 5' in text
        # per-stage attribution children bound as stages appeared
        assert 'slo_stage_p50_ms{stage="device-collect"} 10' in text
        # INACTIVE rungs never report 0/1 — `slo_ok == 0` is alertable
        # without an slo_active conjunction (a 1080p60 pod must not
        # page the 4k30 rung, and vice versa)
        assert 'slo_ok{rung="4k30"} -1' in text
        assert 'slo_ok{rung="8x1080p60"} -1' in text

    def test_global_registry_has_slo_families(self):
        text = obsm.REGISTRY.render()
        for family in ("slo_ok", "slo_budget_ms", "slo_p50_ms",
                       "slo_link_rtt_ms", "slo_stage_p50_ms"):
            assert f"# TYPE {family} gauge" in text

    def test_render_text_names_over_budget_stage(self):
        led = obsb.BudgetLedger()
        led.set_context(1920, 1080, 60)
        feed(led)
        led.set_link_rtt(5.0)
        txt = obsb.render_budget_text(led)
        assert "device-collect" in txt
        assert "compute p50" in txt and "link rtt" in txt
        assert "1080p60 *" in txt


class _FakeEncoder:
    """Pipelined-API stand-in: no device, no compile; emits one 'AU'
    per frame so the whole session/mux/fan-out/ws path runs for real."""

    def __init__(self):
        self.frame_index = 0

    def encode_submit(self, rgb):
        self.frame_index += 1
        return (self.frame_index, rgb.nbytes)

    def encode_collect(self, token):
        from docker_nvidia_glx_desktop_tpu.models.base import EncodedFrame
        idx, _ = token
        return EncodedFrame(data=b"\xff" * 64, keyframe=True,
                            frame_index=idx, codec="mjpeg",
                            width=64, height=48, encode_ms=1.0)

    def request_keyframe(self):
        pass

    def headers(self):
        return b""


class TestLoopbackBench:
    def test_loopback_emits_well_formed_block(self, monkeypatch):
        """The bench smoke (CI satellite) without XLA: fake encoder,
        real StreamSession + aiohttp server + ws sink."""
        from docker_nvidia_glx_desktop_tpu.web import loopback, session

        monkeypatch.setattr(session, "make_encoder",
                            lambda cfg, w, h: (_FakeEncoder(), "mjpeg"))
        cfg = loopback.serving_budget_config(64, 48, fps=30)

        async def go():
            return await loopback.run_serving_budget(
                cfg, frames=6, probe_link=False, timeout_s=30.0)

        block = run(go())
        assert block["mode"] == "loopback-ws"
        assert block["codec"] == "mjpeg"
        assert block["sink"]["frags"] >= 6
        assert block["frames"] >= 6
        assert block["e2e_p50_ms"] > 0
        stages = block["stages"]
        for stage in ("captured", "device-submit", "device-collect",
                      "bitstream", "publish", "total"):
            assert stage in stages, f"missing stage {stage}"
        rungs = block["rungs"]
        assert "1080p60" in rungs
        active = [r for r in rungs.values() if r["active"]]
        assert len(active) == 1
        assert active[0]["attribution"], "no attribution on active rung"
        json.dumps(block)                   # JSON-able end to end


class DummySession:
    codec_name = "h264_cavlc"
    init_segment = b"INIT"

    class _Src:
        width, height = 64, 48
    source = _Src()

    def subscribe(self, maxsize=8):
        q = asyncio.Queue(maxsize=maxsize)
        q.put_nowait(("init", self.init_segment))
        return q

    def unsubscribe(self, q):
        pass

    def stats_summary(self):
        return {"fps": 1.0}


class TestBudgetEndpoint:
    def _cfg(self):
        return from_env({"ENABLE_BASIC_AUTH": "true", "PASSWD": "sekret",
                         "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "0"})

    def test_debug_budget_auth_exempt_text_and_json(self):
        async def go():
            runner = await serve(self._cfg(), session=DummySession())
            port = bound_port(runner)
            base = f"http://127.0.0.1:{port}"
            try:
                async with ClientSession() as http:
                    async with http.get(base + "/debug/budget") as r:
                        assert r.status == 200     # no password needed
                        text = await r.text()
                    async with http.get(
                            base + "/debug/budget?format=json") as r:
                        assert r.status == 200
                        doc = await r.json()
            finally:
                await runner.cleanup()
            return text, doc

        text, doc = run(go())
        assert "serving budget ledger" in text
        assert "rungs" in doc and "1080p60" in doc["rungs"]
        assert doc["window"] == obsb.WINDOW

    def test_stats_embeds_serving_budget(self):
        from aiohttp import BasicAuth

        async def go():
            runner = await serve(self._cfg(), session=DummySession())
            port = bound_port(runner)
            try:
                async with ClientSession() as http:
                    async with http.get(
                            f"http://127.0.0.1:{port}/stats",
                            auth=BasicAuth("u", "sekret")) as r:
                        assert r.status == 200
                        return await r.json()
            finally:
                await runner.cleanup()

        stats = run(go())
        assert "rungs" in stats["serving_budget"]


class TestProcStats:
    def test_peak_rss_gauge(self):
        from docker_nvidia_glx_desktop_tpu.obs import procstats

        reg = obsm.Registry()
        procstats.register_process_gauges(reg)
        text = reg.render()
        assert "# TYPE process_peak_rss_bytes gauge" in text
        g = reg.get("process_peak_rss_bytes")
        assert g.value > 1e6                # > 1 MB: a real process

    def test_cache_counters_and_derived_misses(self):
        from docker_nvidia_glx_desktop_tpu.obs import procstats

        reg = obsm.Registry()
        procstats.register_process_gauges(reg)
        reg.get("jax_compile_cache_requests_total").inc(5)
        reg.get("jax_compile_cache_hits_total").inc(3)
        assert reg.get("jax_compile_cache_misses_total").value == 2

    def test_log_startup_returns_numbers(self):
        from docker_nvidia_glx_desktop_tpu.obs import procstats

        stats = procstats.log_startup()
        assert stats["peak_rss_mb"] > 1
        assert stats["jax_cache_misses"] >= 0

    def test_listener_registration_idempotent(self):
        from docker_nvidia_glx_desktop_tpu.obs import procstats

        first = procstats.register_jax_cache_listener()
        again = procstats.register_jax_cache_listener()
        assert first == again               # second call is a no-op


def test_frame_feed_matches_session_mark_names():
    """The ledger's stage set and web/session's mark names must not
    drift: session.py records exactly these marks per frame."""
    import inspect

    from docker_nvidia_glx_desktop_tpu.web import session

    src = inspect.getsource(session.StreamSession._run) + inspect.getsource(
        session.StreamSession._collect_oldest)
    for mark in ("capture", "captured", "device-submit",
                 "device-collect", "bitstream", "publish"):
        assert f'("{mark}"' in src, f"mark {mark!r} gone from session"
