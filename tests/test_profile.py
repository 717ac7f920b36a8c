"""Kernel profiler + SLO burn-rate plane + provenance (ISSUE 16).

Fast tier: everything here runs on private KernelProfiler/BurnEngine
instances with injected timestamps and compile sequences — no XLA
compiles, no device work.  The real encoder-driven histogram test
(intra/p submit+collect on the CPU backend) lives in
test_profile_device.py (slow tier).
"""

import asyncio
import json
import threading

import pytest
from aiohttp import ClientSession

from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import profile as obsp
from docker_nvidia_glx_desktop_tpu.obs import provenance as obspv
from docker_nvidia_glx_desktop_tpu.obs import slo as obss
from docker_nvidia_glx_desktop_tpu.obs.budget import LEDGER


# ---------------------------------------------------------------------------
# KernelProfiler
# ---------------------------------------------------------------------------

class TestKernelProfiler:

    def _prof(self, **kw):
        p = obsp.KernelProfiler(**kw)
        p._backend = "testbe"   # skip the jax backend resolve
        return p

    def test_chunk_amortization(self):
        """A chunk-dispatch slot's big pull is spread over chunk_len
        frames — the per-frame histogram must read K honest costs, not
        one outlier (same contract as the journey accounting)."""
        p = self._prof()
        p.record("p-collect", 80.0, chunk_len=4)
        s = p.stage_summary()["p-collect"]
        assert s["n"] == 1
        assert s["p50"] == pytest.approx(20.0)

    def test_cold_then_steady_then_recompile(self):
        """First sample of a stage is cold; subsequent samples are
        steady until a backend compile bumps the sequence, which marks
        exactly the next sample per stage cold again."""
        p = self._prof()
        p.record("s", 1.0)
        p.record("s", 1.0)
        p.record("s", 1.0)
        phases = [e[3] for e in p._ring]
        assert phases == ["cold", "steady", "steady"]
        p.on_compile_duration(
            "/jax/core/compile/backend_compile_duration", 0.5)
        p.record("s", 1.0)
        p.record("s", 1.0)
        phases = [e[3] for e in p._ring]
        assert phases == ["cold", "steady", "steady", "cold", "steady"]

    def test_only_backend_compile_bumps_sequence(self):
        """jaxpr tracing re-fires on cache hits — it must be observed on
        the compile histogram but NOT flip warm frames to cold."""
        p = self._prof()
        p.record("s", 1.0)
        p.record("s", 1.0)
        seq = p._compile_seq
        p.on_compile_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.1)
        assert p._compile_seq == seq           # no bump
        p.record("s", 1.0)
        assert list(p._ring)[-1][3] == "steady"
        # non-compile events are ignored entirely
        p.on_compile_duration("/jax/core/something_else", 0.1)
        assert len(p._compiles) == 1

    def test_steady_only_p50_excludes_cold(self):
        p = self._prof()
        for _ in range(5):                     # recompile storm: every
            p._compile_seq += 1                # first-after-compile
            p.record("s", 1000.0)              # sample is a cold outlier
        for _ in range(4):
            p.record("s", 2.0)
        assert p.stage_p50s()["s"] == pytest.approx(1000.0)
        assert p.stage_p50s(steady_only=True)["s"] == pytest.approx(2.0)

    def test_record_encoder_pulls_labels(self):
        class Enc:
            codec = "h264_cavlc"
            width, height = 640, 480
            tune = "hq"
            _spatial_nx = 2

        p = self._prof()
        p.record_encoder(Enc(), "intra-collect", 12.0)
        (_, stage, ms, phase, codec, geometry, tune, shards) = \
            list(p._ring)[0]
        assert (stage, codec, geometry, tune, shards) == \
            ("intra-collect", "h264_cavlc", "640x480", "hq", 2)

    def test_disabled_switch_is_total(self):
        p = self._prof()
        obsp.set_enabled(False)
        try:
            p.record("s", 1.0)
            p.record_encoder(object(), "s", 1.0)
            assert len(p._ring) == 0
        finally:
            obsp.set_enabled(True)
        assert obsp.enabled()

    def test_ring_bounded(self):
        p = self._prof(capacity=8)
        for i in range(100):
            p.record("s", float(i))
        assert len(p._ring) == 8

    def test_chrome_trace_structure(self):
        p = self._prof()
        p.record("p-collect", 10.0, codec="h264", chunk_len=2)
        p.on_compile_duration(
            "/jax/core/compile/backend_compile_duration", 0.25)
        doc = p.export_chrome_trace()
        events = doc["traceEvents"]
        assert any(e["ph"] == "M" for e in events)
        xs = [e for e in events if e["ph"] == "X"]
        tids = {e["tid"] for e in xs}
        assert "stage:p-collect" in tids
        assert "xla-compile" in tids
        assert all(e["dur"] >= 0 and isinstance(e["ts"], (int, float))
                   for e in xs)
        assert doc["otherData"]["compiles"]["backend_compiles"] == 1
        json.dumps(doc)                        # Perfetto-openable = JSON

    def test_snapshot_shape_and_clear(self):
        p = self._prof()
        p.record("s", 5.0)
        snap = p.snapshot()
        for key in ("enabled", "backend", "samples", "stages",
                    "stage_p50_ms", "stage_p50_ms_steady", "compiles"):
            assert key in snap
        assert snap["samples"] == 1
        json.dumps(snap)
        p.clear()
        assert p.snapshot()["samples"] == 0
        # after clear the stage is "first seen" again -> cold
        p.record("s", 5.0)
        assert list(p._ring)[0][3] == "cold"


# ---------------------------------------------------------------------------
# Burn windows / engine
# ---------------------------------------------------------------------------

class TestBurnEngine:

    def test_no_data(self):
        assert obss.BurnEngine().verdict(t=100.0)["severity"] == "no_data"

    def _fill(self, eng, bad, good, t=1000.0):
        eng.record(True, t=t, n=bad)
        eng.record(False, t=t, n=good)
        return eng.verdict(t=t)

    def test_ok_at_burn_one(self):
        """1% bad at a 99% target = burn 1.0 — spending the error budget
        exactly on schedule is ok, not an alert."""
        v = self._fill(obss.BurnEngine(), bad=1, good=99)
        assert v["windows"]["fast_5m"]["burn_rate"] == pytest.approx(1.0)
        assert v["severity"] == "ok"

    def test_warn_between_six_and_page(self):
        v = self._fill(obss.BurnEngine(), bad=10, good=90)
        assert v["windows"]["fast_5m"]["burn_rate"] == pytest.approx(10.0)
        assert v["severity"] == "warn"

    def test_page_at_fourteen_four(self):
        v = self._fill(obss.BurnEngine(), bad=20, good=80)
        assert v["windows"]["slow_1h"]["burn_rate"] == pytest.approx(20.0)
        assert v["severity"] == "page"

    def test_multi_window_rule_needs_both(self):
        """A burst that has aged out of the fast window must not page
        even though the slow window still burns hot — the fast window
        is what clears the alert once the problem is fixed."""
        eng = obss.BurnEngine()
        eng.record(True, t=100.0, n=50)        # old burst
        eng.record(False, t=100.0, n=50)
        # 20 min later: fast window (5 m) has rolled past the burst,
        # slow window (1 h) still sees it
        eng.record(False, t=1300.0, n=10)
        v = eng.verdict(t=1300.0)
        assert v["windows"]["slow_1h"]["burn_rate"] >= obss.PAGE_BURN
        assert v["windows"]["fast_5m"]["burn_rate"] == pytest.approx(0.0)
        assert v["severity"] == "ok"

    def test_window_expiry_exact(self):
        eng = obss.BurnEngine()
        eng.record(True, t=10.0)
        frames, bad = eng.fast.totals(t=10.0 + obss.FAST_WINDOW_S + 20)
        assert (frames, bad) == (0, 0)
        frames, bad = eng.slow.totals(t=10.0 + obss.FAST_WINDOW_S + 20)
        assert (frames, bad) == (1, 1)


# ---------------------------------------------------------------------------
# SloPlane against the BASELINE ladder
# ---------------------------------------------------------------------------

@pytest.fixture
def ledger_1080p60():
    """Point the global ledger at the flagship rung (1080p60, 20 ms)
    with a 2 ms measured link, restoring the prior context after."""
    old_ctx, old_link = LEDGER.context(), LEDGER.link_rtt_ms
    LEDGER.set_context(1920, 1080, 60)
    LEDGER.set_link_rtt(2.0)
    yield LEDGER
    LEDGER.clear_context()
    if old_ctx is not None:
        LEDGER.set_context(*old_ctx)
    LEDGER._link_rtt_ms = old_link


class TestSloPlane:

    def test_flagship_rung_verdicts(self, ledger_1080p60):
        """/debug/slo shape for the BASELINE 1080p rung: link-separated
        totals judged against the 20 ms bar, per-session + fleet."""
        plane = obss.SloPlane()
        plane.record("s1", 25.0, t=1000.0)     # 25-2=23 > 20 -> bad
        plane.record("s1", 15.0, t=1000.0)     # 13 <= 20 -> good
        plane.record("s2", 10.0, t=1000.0)
        v = plane.verdicts(t=1000.0)
        assert v["rung"]["name"] == "1080p60"
        assert v["rung"]["budget_ms"] == 20.0
        assert v["link_rtt_ms"] == 2.0
        assert v["thresholds"] == {"page_burn": 14.4, "warn_burn": 6.0,
                                   "rule": "both windows over threshold"}
        assert v["sessions"]["s1"]["over_total"] == 1
        assert v["sessions"]["s1"]["frames_total"] == 2
        assert v["sessions"]["s2"]["over_total"] == 0
        assert v["fleet"]["frames_total"] == 3
        json.dumps(v)

    def test_no_rung_means_no_judgement(self):
        old_ctx = LEDGER.context()
        LEDGER.clear_context()
        try:
            plane = obss.SloPlane()
            plane.record("s1", 9999.0, t=1.0)
            assert plane.fleet.frames == 0
        finally:
            if old_ctx is not None:
                LEDGER.set_context(*old_ctx)

    def test_trace_marks_chunk_amortized(self, ledger_1080p60):
        """A chunked marks entry counts as chunk_len frames at the
        amortized per-frame cost — an 80 ms chunk of 4 is four good
        18 ms frames against the 20 ms bar, not one terrible 80 ms one."""
        plane = obss.SloPlane()
        meta = (("session", "bs"), ("chunk_len", 4))
        marks = (("capture", 0.0), ("publish", 0.080))
        plane._on_trace("marks", (1, marks, 0, meta))
        v = plane.verdicts(t=None)["sessions"]["bs"]
        assert v["frames_total"] == 4
        assert v["over_total"] == 0
        # 120 ms chunk of 4 -> 30-2=28 ms each -> all 4 over
        plane._on_trace(
            "marks", (2, (("capture", 0.0), ("publish", 0.120)), 0, meta))
        v = plane.verdicts(t=None)["sessions"]["bs"]
        assert (v["frames_total"], v["over_total"]) == (8, 4)

    def test_session_cap_evicts_oldest(self, ledger_1080p60):
        plane = obss.SloPlane()
        for i in range(obss.MAX_SESSIONS + 5):
            plane.record(f"s{i}", 1.0, t=10.0)
        assert len(plane._sessions) == obss.MAX_SESSIONS
        assert "s0" not in plane._sessions
        plane.drop_session("s7")
        assert "s7" not in plane._sessions

    def test_burn_gauges_render(self, ledger_1080p60):
        reg = obsm.Registry()
        plane = obss.SloPlane()
        plane.record("s1", 100.0, t=50.0)      # over -> nonzero burn
        obss.register_slo_burn_gauges(plane=plane, registry=reg)
        text = reg.render()
        assert 'dngd_slo_burn_rate{scope="fleet",window="fast_5m"}' in text
        assert "dngd_slo_burn_severity" in text

    def test_module_snapshot_is_debug_slo_payload(self):
        snap = obss.snapshot()
        for key in ("target", "thresholds", "rung", "fleet", "sessions"):
            assert key in snap
        json.dumps(snap)


# ---------------------------------------------------------------------------
# Series-overflow counter (satellite: cardinality-cap observability)
# ---------------------------------------------------------------------------

class TestSeriesOverflowCounter:

    def test_overflow_counted_per_collapsed_resolution(self):
        reg = obsm.Registry()
        c = obsm.Counter("cap_total", "h", ("k",), registry=reg,
                         max_series=3)
        for i in range(10):
            c.labels(f"v{i}").inc()
        ov = reg.get(obsm.OVERFLOW_COUNTER)
        # 3 cached, 7 distinct keys collapsed into `other`
        assert ov.labels("cap_total").value == 7
        assert 'dngd_metrics_series_overflow_total{metric="cap_total"} 7' \
            in reg.render()

    def test_overflow_counter_itself_never_overflows_recursively(self):
        reg = obsm.Registry()
        # the overflow counter collapsing must not try to count itself
        ov = obsm.Counter(obsm.OVERFLOW_COUNTER, "h", ("metric",),
                          registry=reg, max_series=2)
        for i in range(10):
            ov.labels(f"m{i}").inc()
        assert len(list(ov.series())) <= 3

    def test_concurrent_hammering_of_the_cap(self):
        """Satellite contract: N threads racing distinct label sets past
        the cap — every increment lands somewhere (cap series or
        `other`), the series count stays bounded, and the overflow
        counter accounts for exactly the collapsed resolutions."""
        reg = obsm.Registry()
        cap = 4
        threads_n, per_thread = 8, 50
        c = obsm.Counter("hammer_total", "h", ("k",), registry=reg,
                         max_series=cap)
        barrier = threading.Barrier(threads_n)
        errors = []

        def worker(tid):
            try:
                barrier.wait(timeout=10)
                for i in range(per_thread):
                    c.labels(f"t{tid}-{i}").inc()
            except Exception as e:          # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        series = list(c.series())
        assert len(series) <= cap + 1          # cap + the `other` series
        total = threads_n * per_thread
        assert sum(child.value for _, child in series) == total
        ov = reg.get(obsm.OVERFLOW_COUNTER).labels("hammer_total").value
        # every distinct key is resolved exactly once; whichever `cap`
        # keys won the cache slots, the rest collapsed — and were counted
        assert ov == total - cap


# ---------------------------------------------------------------------------
# Provenance
# ---------------------------------------------------------------------------

class TestProvenance:

    def test_provenance_block_shape(self):
        blk = obspv.provenance_block()
        for key in ("schema", "ts_unix", "git_sha", "versions",
                    "topology", "host", "env"):
            assert key in blk
        assert blk["schema"] == 1
        assert "python" in blk["versions"]
        assert isinstance(blk["env"], dict)
        json.dumps(blk)

    def test_git_sha_resolves_here(self):
        sha = obspv.git_sha()
        assert sha and len(sha) == 40
        short = obspv.git_sha(short=True)
        assert short and sha.startswith(short)

    def test_env_knobs_prefix_filter(self, monkeypatch):
        monkeypatch.setenv("DNGD_TESTKNOB", "7")
        monkeypatch.setenv("ENCODER_TUNE", "hq")
        monkeypatch.setenv("UNRELATED_SECRET", "nope")
        knobs = obspv.env_knobs()
        assert knobs["DNGD_TESTKNOB"] == "7"
        assert knobs["ENCODER_TUNE"] == "hq"
        assert "UNRELATED_SECRET" not in knobs

    def test_bench_snapshot_embeds_all_planes(self):
        snap = obspv.bench_snapshot()
        assert "provenance" in snap
        assert "profile" in snap
        assert "slo" in snap
        json.dumps(snap)


# ---------------------------------------------------------------------------
# /debug/profile + /debug/slo over the web server (auth-exempt)
# ---------------------------------------------------------------------------

class TestHttpEndpoints:

    def _serve_and_get(self, paths):
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env
        from docker_nvidia_glx_desktop_tpu.web.server import (
            bound_port, serve)
        from test_obs import DummySession

        cfg = from_env({"ENABLE_BASIC_AUTH": "true", "PASSWD": "sekret",
                        "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "0"})

        async def go():
            runner = await serve(cfg, session=DummySession())
            port = bound_port(runner)
            out = {}
            try:
                async with ClientSession() as http:
                    for path in paths:
                        async with http.get(
                                f"http://127.0.0.1:{port}{path}") as r:
                            assert r.status == 200, path
                            out[path] = await r.json(content_type=None)
            finally:
                await runner.cleanup()
            return out

        return asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 30))

    def test_debug_profile_and_slo(self):
        obsp.PROFILER.record("p-collect", 7.0, codec="h264_cavlc",
                             geometry="64x48")
        docs = self._serve_and_get(["/debug/profile",
                                    "/debug/profile?format=json",
                                    "/debug/slo"])
        trace = docs["/debug/profile"]
        assert any(e.get("tid") == "stage:p-collect"
                   for e in trace["traceEvents"])
        snap = docs["/debug/profile?format=json"]
        assert "p-collect" in snap["stages"]
        assert "stage_p50_ms_steady" in snap
        slo = docs["/debug/slo"]
        for key in ("target", "thresholds", "fleet", "sessions"):
            assert key in slo
