"""Unified telemetry tests: registry semantics, Prometheus exposition,
auth-exempt /metrics, Chrome trace export, and the instrumented layers
(supervisor restarts, RTCP RR gauges, TURN relay counters, subscriber
drop accounting)."""

import asyncio
import json
import re
import struct

import pytest
from aiohttp import BasicAuth, ClientSession, WSMsgType

from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.obs.http import PROM_CONTENT_TYPE
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
from docker_nvidia_glx_desktop_tpu.utils.timing import StageTimer
from docker_nvidia_glx_desktop_tpu.web.server import bound_port, serve
from docker_nvidia_glx_desktop_tpu.webrtc import rtcp, stun, turn_client


def run(coro):
    return asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(coro, 30))


class TestRegistry:
    """Counter/Gauge/Histogram semantics in a private registry."""

    def test_counter_and_labels(self):
        reg = obsm.Registry()
        c = obsm.Counter("c_total", "help", ("k",), registry=reg)
        c.labels("a").inc()
        c.labels("a").inc(2)
        c.labels("b").inc()
        assert c.labels("a").value == 3
        assert c.labels("b").value == 1

    def test_gauge_set_function(self):
        reg = obsm.Registry()
        g = obsm.Gauge("g", "help", registry=reg)
        g.set(5)
        assert g.value == 5
        g.set_function(lambda: 42)
        assert g.value == 42
        assert "g 42" in reg.render()

    def test_histogram_bucket_edges_inclusive(self):
        """Prometheus contract: le is INCLUSIVE (v <= edge)."""
        reg = obsm.Registry()
        h = obsm.Histogram("h_ms", "help", buckets=(1.0, 10.0),
                           registry=reg)
        h.observe(1.0)       # exactly on an edge -> le="1" bucket
        h.observe(5.0)
        h.observe(100.0)     # overflows into +Inf only
        text = reg.render()
        assert 'h_ms_bucket{le="1"} 1' in text
        assert 'h_ms_bucket{le="10"} 2' in text
        assert 'h_ms_bucket{le="+Inf"} 3' in text
        assert "h_ms_count 3" in text
        assert "h_ms_sum 106" in text

    def test_label_cardinality_cap(self):
        """Past the cap, new label sets collapse into one 'other' series
        instead of growing without bound."""
        reg = obsm.Registry()
        c = obsm.Counter("cap_total", "help", ("k",), registry=reg,
                         max_series=3)
        for i in range(10):
            c.labels(f"v{i}").inc()
        assert len(list(c.series())) <= 4      # 3 + the overflow series
        overflow = c.labels("brand-new-value")  # routed to overflow
        assert overflow is c.labels("another-new-value")

    def test_duplicate_name_rejected(self):
        reg = obsm.Registry()
        obsm.Counter("dup_total", "help", registry=reg)
        with pytest.raises(ValueError):
            obsm.Counter("dup_total", "help", registry=reg)

    def test_get_or_create_idempotent(self):
        reg = obsm.Registry()
        a = obsm.counter("x_total", "help", registry=reg)
        b = obsm.counter("x_total", "help", registry=reg)
        assert a is b
        with pytest.raises(ValueError):
            obsm.gauge("x_total", "help", registry=reg)   # kind mismatch

    def test_exposition_format_parses(self):
        """Every non-comment line is `name{labels} value` with a float-
        parseable value — the exposition-format contract a Prometheus
        scraper relies on."""
        reg = obsm.Registry()
        obsm.Counter("a_total", "ca", ("x",), registry=reg).labels(
            'we"ird\nval').inc()
        obsm.Gauge("b", "gb", registry=reg).set(1.5)
        h = obsm.Histogram("c_ms", "hc", registry=reg)
        h.observe(3.0)
        line_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
            r'(\+Inf|-?[0-9.e+-]+)$')
        lines = reg.render().splitlines()
        assert lines, "empty exposition"
        seen_types = {}
        for ln in lines:
            if ln.startswith("# TYPE"):
                _, _, name, kind = ln.split()
                seen_types[name] = kind
                continue
            if ln.startswith("#") or not ln:
                continue
            assert line_re.match(ln), f"unparseable line: {ln!r}"
        assert seen_types == {"a_total": "counter", "b": "gauge",
                              "c_ms": "histogram"}

    def test_snapshot_is_jsonable_view(self):
        reg = obsm.Registry()
        obsm.Counter("j_total", "help", registry=reg).inc(7)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["j_total"]["series"][0]["value"] == 7


class TestTrace:
    def test_stage_timer_flush_and_export(self):
        rec = obst.TraceRecorder("t1")
        st = StageTimer()
        st.mark("capture")
        st.mark("device-submit")
        st.mark("publish")
        fid = obst.next_frame_id()
        st.flush_to(rec, fid)
        assert st.stamps == {}                 # reset for the next frame
        rec.record_span("rtp-sent", 1.0, 0.002, fid)
        doc = obst.export_chrome_trace([rec])
        text = json.dumps(doc)                 # valid JSON end to end
        doc = json.loads(text)
        events = doc["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        # 2 spans from 3 marks + 1 explicit span
        assert len(xs) == 3
        for e in xs:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["args"]["frame"] == fid
        names = {e["name"] for e in xs}
        assert names == {"device-submit", "publish", "rtp-sent"}

    def test_pts_is_the_cross_track_correlation_key(self):
        """Encode-thread marks and webrtc rtp-sent spans of one frame
        must share args.pts so Perfetto can correlate the tracks."""
        rec = obst.TraceRecorder("t3")
        rec.record_marks(5, (("a", 0.0), ("b", 0.1)), pts=90_000)
        rec.record_span("rtp-sent", 0.2, 0.01, pts=90_000)
        xs = [e for e in rec.chrome_events() if e["ph"] == "X"]
        assert len(xs) == 2
        assert all(e["args"]["pts"] == 90_000 for e in xs)

    def test_ring_buffer_bounded(self):
        rec = obst.TraceRecorder("t2", capacity=8)
        for i in range(100):
            rec.record_span("s", float(i), 0.1, i)
        assert len(rec.chrome_events()) == 8


def _stage_counts(name):
    """(_sum, _count) of ``dngd_stage_<name>_ms`` as /metrics renders
    them, read the way benchmark/run.py:parse_metrics reads a family."""
    out = {}
    for line in obsm.REGISTRY.render().splitlines():
        for suffix in ("_sum", "_count"):
            if line.startswith(f"dngd_stage_{name}_ms{suffix} "):
                out[suffix] = float(line.rpartition(" ")[2])
    return out["_sum"], out["_count"]


class TestStageSpans:
    """obs/trace.stage: a profiler span and one histogram sample, and
    nothing in any recorder."""

    @pytest.mark.parametrize("name", obst.STAGES)
    def test_stage_observes_into_its_own_unlabelled_family(self, name):
        obst._carry.__dict__.clear()        # no split stage left open
        s0, n0 = _stage_counts(name)        # registered at import: renders
        with obst.stage(name) as span:
            pass
        s1, n1 = _stage_counts(name)
        assert n1 == n0 + 1
        assert span.ms > 0 and s1 - s0 == pytest.approx(span.ms)
        fam = obsm.REGISTRY.get(f"dngd_stage_{name}_ms")
        assert fam.kind == "histogram" and fam.labelnames == ()
        assert fam.edges == obst.STAGE_BUCKETS_MS
        assert fam.edges[0] == 0.25 and 50.0 in fam.edges

    def test_a_new_name_gets_its_family_on_first_use(self):
        with obst.stage("unit_test_only"):
            pass
        assert _stage_counts("unit_test_only")[1] == 1

    def test_disabled_is_an_early_return(self):
        _, n0 = _stage_counts("pull")
        obst.set_enabled(False)
        try:
            with obst.stage("pull") as span:
                pass
            with obst.stage("assemble", more=True):
                pass
        finally:
            obst.set_enabled(True)
        assert span.ms == 0.0 and span._ann is None
        assert _stage_counts("pull")[1] == n0
        assert "dngd.assemble" not in obst._carry.__dict__

    def test_a_split_stage_takes_one_sample_for_both_parts(self):
        s0, n0 = _stage_counts("assemble")
        with obst.stage("assemble", more=True) as first:
            pass
        assert _stage_counts("assemble") == (s0, n0)    # handed on
        with obst.stage("assemble") as last:
            pass
        s1, n1 = _stage_counts("assemble")
        assert n1 == n0 + 1
        assert s1 - s0 == pytest.approx(first.ms + last.ms)
        # the first part of a frame that never closes is dropped by the
        # next frame's, not added to it
        with obst.stage("assemble", more=True):
            pass
        with obst.stage("assemble", more=True) as first:
            pass
        with obst.stage("assemble") as last:
            pass
        assert _stage_counts("assemble")[0] - s1 == pytest.approx(
            first.ms + last.ms)

    def test_an_exception_closes_the_span_and_passes(self):
        _, n0 = _stage_counts("dispatch")
        with pytest.raises(RuntimeError):
            with obst.stage("dispatch"):
                raise RuntimeError("device gone")
        assert _stage_counts("dispatch")[1] == n0 + 1

    def test_the_span_is_a_profiler_annotation(self, monkeypatch):
        seen = []

        class Ann:
            def __init__(self, name):
                seen.append(("init", name))

            def __enter__(self):
                seen.append("enter")

            def __exit__(self, *exc):
                seen.append("exit")

        monkeypatch.setattr(obst, "_annotation", Ann)
        with obst.stage("colour"):
            seen.append("body")
        assert seen == [("init", "dngd.colour"), "enter", "body", "exit"]
        monkeypatch.setattr(obst, "_annotation", None)
        import jax
        assert obst._load_annotation() is jax.profiler.TraceAnnotation

    def test_10000_frames_of_stages_touch_no_recorder(self):
        """The per-frame marks, their ring (4,096: an overwrite counts
        as a drop) and their listeners are as they were: a stage span
        records nothing there."""
        rec = obst.tracer("pipeline")
        entries = []
        listener = lambda kind, entry: entries.append(kind)   # noqa: E731
        rec.add_listener(listener)
        try:
            before, dropped = len(rec), obst.dropped_total()
            for _ in range(10_000):
                for name in obst.STAGES:
                    with obst.stage(name):
                        pass
            assert len(rec) == before and entries == []
            assert obst.dropped_total() == dropped
        finally:
            rec.remove_listener(listener)


class DummySource:
    width, height = 64, 48


class DummySession:
    codec_name = "h264_cavlc"
    source = DummySource()
    init_segment = b"INIT"

    def subscribe(self, maxsize=8):
        q = asyncio.Queue(maxsize=maxsize)
        q.put_nowait(("init", self.init_segment))
        return q

    def unsubscribe(self, q):
        pass

    def stats_summary(self):
        return {"fps": 1.0}


class TestHttpExposition:
    """/metrics and /debug/trace on the web server: auth-exempt (like
    /healthz), correct content type, containing the instrumented
    families."""

    def _cfg(self):
        return from_env({"ENABLE_BASIC_AUTH": "true", "PASSWD": "sekret",
                         "LISTEN_ADDR": "127.0.0.1", "LISTEN_PORT": "0"})

    def test_metrics_auth_exempt_and_families(self):
        # importing the instrumented layers registers their families
        import docker_nvidia_glx_desktop_tpu.platform.supervisor  # noqa: F401
        import docker_nvidia_glx_desktop_tpu.web.session  # noqa: F401

        async def go():
            runner = await serve(self._cfg(), session=DummySession())
            port = bound_port(runner)
            base = f"http://127.0.0.1:{port}"
            try:
                async with ClientSession() as http:
                    # unauthenticated: /stats challenges, /metrics serves
                    async with http.get(base + "/stats") as r:
                        assert r.status == 401
                    async with http.get(base + "/metrics") as r:
                        assert r.status == 200
                        assert r.headers["Content-Type"] == \
                            PROM_CONTENT_TYPE
                        text = await r.text()
                    async with http.get(base + "/debug/trace") as r:
                        assert r.status == 200
                        doc = await r.json()
                    # authed /stats embeds the registry snapshot
                    async with http.get(
                            base + "/stats",
                            auth=BasicAuth("u", "sekret")) as r:
                        assert r.status == 200
                        stats = await r.json()
            finally:
                await runner.cleanup()
            return text, doc, stats

        text, doc, stats = run(go())
        for family in ("dngd_encoder_submit_ms",
                       "dngd_encoder_collect_ms",
                       "dngd_supervisor_restarts_total",
                       "dngd_session_queue_depth",
                       "dngd_session_dropped_frags_total"):
            assert f"# TYPE {family}" in text, f"missing {family}"
        assert isinstance(doc["traceEvents"], list)
        assert "dngd_encoder_submit_ms" in stats["metrics"]

    def test_trace_endpoint_is_chrome_trace_json(self):
        rec = obst.tracer("pipeline")
        st = StageTimer()
        st.mark("capture")
        st.mark("device-submit")
        st.flush_to(rec, obst.next_frame_id())

        async def go():
            runner = await serve(self._cfg(), session=DummySession())
            port = bound_port(runner)
            try:
                async with ClientSession() as http:
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/trace") as r:
                        return await r.json()
            finally:
                await runner.cleanup()

        doc = run(go())
        events = doc["traceEvents"]
        assert any(e["ph"] == "M" and e["args"]["name"] == "pipeline"
                   for e in events)
        xs = [e for e in events if e["ph"] == "X"]
        assert xs and all(
            isinstance(e["ts"], (int, float)) and e["dur"] >= 0
            for e in xs)

    def test_metrics_on_rfb_bridge(self):
        """The websock (noVNC) port exposes the same registry."""
        # importing the rfb server registers its metric families
        import docker_nvidia_glx_desktop_tpu.rfb.server  # noqa: F401
        from docker_nvidia_glx_desktop_tpu.rfb import websock

        async def go():
            runner = await websock.serve_bridge("127.0.0.1", 0)
            port = websock.bound_port(runner)
            try:
                async with ClientSession() as http:
                    async with http.get(
                            f"http://127.0.0.1:{port}/metrics") as r:
                        assert r.status == 200
                        return await r.text()
            finally:
                await runner.cleanup()

        text = run(go())
        assert "# TYPE dngd_rfb_clients gauge" in text


class TestSupervisorMetrics:
    def test_restart_counter_increments_on_crash(self, tmp_path):
        from docker_nvidia_glx_desktop_tpu.platform.supervisor import (
            _M_CRASH_LOOPS, _M_RESTARTS, Program, Supervisor)

        restarts0 = _M_RESTARTS.labels("obs-crasher").value
        crashes0 = _M_CRASH_LOOPS.labels("obs-crasher").value

        async def go():
            sup = Supervisor(logdir=str(tmp_path))
            sup.add(Program("obs-crasher", ["/bin/sh", "-c", "exit 1"],
                            backoff_initial=0.01, backoff_max=0.02))
            await sup.start()
            st = sup.state("obs-crasher")
            for _ in range(200):
                if st.restarts >= 2:
                    break
                await asyncio.sleep(0.05)
            await sup.stop()
            return st.restarts

        restarts = run(go())
        assert restarts >= 2
        assert (_M_RESTARTS.labels("obs-crasher").value
                - restarts0) >= 2
        # a program dying at launch is by definition inside the 5s
        # crash-loop window
        assert (_M_CRASH_LOOPS.labels("obs-crasher").value
                - crashes0) >= 2

    def test_status_reports_uptime(self, tmp_path):
        from docker_nvidia_glx_desktop_tpu.platform.supervisor import (
            Program, Supervisor)

        async def go():
            sup = Supervisor(logdir=str(tmp_path))
            sup.add(Program("obs-sleeper", ["/bin/sh", "-c", "sleep 30"]))
            await sup.start()
            await asyncio.sleep(0.2)
            status = sup.status()
            await sup.stop()
            return status

        status = run(go())
        assert status["obs-sleeper"]["uptime_s"] > 0


class TestRtcpIngestion:
    """RR -> per-peer gauges (crypto-free path; the peer feeds the same
    monitor after unprotect_rtcp)."""

    def test_rr_parse_roundtrip(self):
        rr = rtcp.receiver_report(0x42, [
            {"ssrc": 0x1111, "fraction_lost": 128, "cum_lost": 9,
             "highest_seq": 1000, "jitter": 450, "lsr": 7, "dlsr": 3}])
        pkts = rtcp.parse_compound(rr)
        assert len(pkts) == 1 and pkts[0]["pt"] == 201
        blk = pkts[0]["blocks"][0]
        assert blk["ssrc"] == 0x1111
        assert blk["fraction_lost"] == 128
        assert blk["cum_lost"] == 9
        assert blk["jitter"] == 450

    def test_monitor_updates_gauges(self):
        ssrc = 0xDEAD01
        mon = rtcp.PeerRtcpMonitor({ssrc: ("video", 90_000)})
        # lsr/dlsr chosen so rtt = 0.25 s at the given now_mid32
        lsr, dlsr = 100_000, 50_000
        now = lsr + dlsr + (65536 // 4)
        rr = rtcp.receiver_report(0x42, [
            {"ssrc": ssrc, "fraction_lost": 64, "jitter": 9000,
             "lsr": lsr, "dlsr": dlsr}])
        assert mon.ingest(rr, now_mid32=now) == 1
        key = str(ssrc)
        reg = obsm.REGISTRY
        assert reg.get("dngd_webrtc_rtt_ms").labels(
            key, "video").value == pytest.approx(250.0)
        assert reg.get("dngd_webrtc_fraction_lost").labels(
            key, "video").value == pytest.approx(0.25)
        assert reg.get("dngd_webrtc_jitter_ms").labels(
            key, "video").value == pytest.approx(100.0)
        summ = mon.summary()[key]
        assert summ["rtt_ms"] == pytest.approx(250.0)

    def test_monitor_close_removes_per_peer_series(self):
        """Closed peers must not leave stale SSRC gauges behind (they
        would be scraped forever and exhaust the cardinality cap)."""
        ssrc = 0xCAFE33
        mon = rtcp.PeerRtcpMonitor({ssrc: ("video", 90_000)})
        mon.ingest(rtcp.receiver_report(1, [{"ssrc": ssrc,
                                             "jitter": 90}]))
        jit = obsm.REGISTRY.get("dngd_webrtc_jitter_ms")
        key = (str(ssrc), "video")
        assert any(k == key for k, _ in jit.series())
        mon.close()
        assert not any(k == key for k, _ in jit.series())

    def test_unknown_ssrc_ignored(self):
        mon = rtcp.PeerRtcpMonitor({1: ("video", 90_000)})
        rr = rtcp.receiver_report(0x42, [{"ssrc": 999}])
        assert mon.ingest(rr) == 0

    def test_sr_blocks_also_ingested(self):
        """Browsers may append report blocks to SRs (RFC 3550 §6.4.1)."""
        ssrc = 0xBEEF02
        mon = rtcp.PeerRtcpMonitor({ssrc: ("video", 90_000)})
        blocks = struct.pack(">IIIIII", ssrc, 32 << 24, 0, 0, 0, 0)
        body = struct.pack(">IIIIII", 0x42, 0, 0, 0, 0, 0) + blocks
        sr = struct.pack(">BBH", 0x81, 200, len(body) // 4) + body
        assert mon.ingest(sr) == 1


class TestTurnRelay:
    def _alloc(self):
        class FakeTransport:
            def __init__(self):
                self.sent = []

            def sendto(self, data, addr=None):
                self.sent.append(data)

            def close(self):
                pass

        alloc = turn_client.TurnAllocation(("127.0.0.1", 3478), "u", "p")
        alloc._transport = FakeTransport()
        return alloc

    def test_send_to_matches_reference_encoding(self):
        """The spliced template must be byte-identical to the
        StunMessage encoding it replaced (same txid)."""
        alloc = self._alloc()
        peer = ("192.0.2.7", 40_000)
        for payload in (b"", b"x", b"ab", b"abc", b"\x80" * 173):
            alloc._transport.sent.clear()
            alloc.send_to(peer, payload)
            wire = alloc._transport.sent[0]
            msg = stun.StunMessage.decode(wire)
            assert msg.mtype == stun.SEND_INDICATION
            assert msg.xor_address(stun.ATTR_XOR_PEER_ADDRESS) == peer
            assert msg.attrs[stun.ATTR_DATA] == payload
            ref = stun.StunMessage(stun.SEND_INDICATION, txid=msg.txid)
            ref.add_xor_address(stun.ATTR_XOR_PEER_ADDRESS, *peer)
            ref.attrs[stun.ATTR_DATA] = payload
            assert wire == ref.encode(fingerprint=False)
        assert len(alloc._send_tmpl) == 1       # template reused

    def test_relay_counters(self):
        before = turn_client._M_RELAY_TX.value
        bytes_before = turn_client._M_RELAY_TX_BYTES.value
        alloc = self._alloc()
        alloc.send_to(("192.0.2.9", 4), b"12345")
        assert turn_client._M_RELAY_TX.value - before == 1
        assert turn_client._M_RELAY_TX_BYTES.value - bytes_before == 5


class TestSubscriberAccounting:
    def test_drop_and_slow_counters(self):
        from docker_nvidia_glx_desktop_tpu.web import session as wsession

        subs = wsession.SubscriberSet()
        q = subs.subscribe(maxsize=2)
        dropped0 = wsession._M_DROPPED.value
        slow0 = wsession._M_SLOW.value
        subs.publish(("frag", b"k", True), keyframe=True)
        subs.publish(("frag", b"p1", False), keyframe=False)
        assert wsession._M_SLOW.value == slow0       # not full yet
        subs.publish(("frag", b"p2", False), keyframe=False)  # evicts
        assert wsession._M_SLOW.value - slow0 == 1
        assert wsession._M_DROPPED.value > dropped0
        assert subs.queue_depth() == q.qsize()

    def test_queue_depth_gauge_live(self):
        from docker_nvidia_glx_desktop_tpu.web import session as wsession

        subs = wsession.SubscriberSet()
        subs.subscribe(maxsize=8)
        subs.publish(("frag", b"k", True), keyframe=True)
        # the scrape-time gauge covers this set (weak-ref registry)
        assert wsession._M_QDEPTH.value >= 1


class TestFrameIds:
    def test_monotonic(self):
        a = obst.next_frame_id()
        b = obst.next_frame_id()
        assert b == a + 1


class TestExpositionFormat:
    """Exposition-format corner cases (PR-2 satellite): escaping rules
    and one-header-per-family, which scrapers hard-require."""

    def test_label_value_escaping(self):
        reg = obsm.Registry()
        c = obsm.Counter("esc_total", "help", ("k",), registry=reg)
        c.labels('back\\slash "quote"\nnewline').inc()
        text = reg.render()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("esc_total{"))
        # backslash escaped FIRST, then \n and ", per format 0.0.4
        assert 'k="back\\\\slash \\"quote\\"\\nnewline"' in line
        # the rendered line must stay a single physical line
        assert "\n" not in line

    def test_help_text_escaping(self):
        reg = obsm.Registry()
        obsm.Counter("h_total", 'multi\nline with back\\slash',
                     registry=reg)
        lines = reg.render().splitlines()
        help_lines = [ln for ln in lines if ln.startswith("# HELP")]
        assert help_lines == [
            "# HELP h_total multi\\nline with back\\\\slash"]

    def test_type_and_help_once_per_family(self):
        """Multiple label sets (and histogram _bucket/_sum/_count
        series) must ride under ONE # TYPE/# HELP pair."""
        reg = obsm.Registry()
        c = obsm.Counter("fam_total", "help", ("k",), registry=reg)
        for v in ("a", "b", "c"):
            c.labels(v).inc()
        h = obsm.Histogram("fam_ms", "help", ("k",),
                           buckets=(1.0, 10.0), registry=reg)
        h.labels("x").observe(0.5)
        h.labels("y").observe(5.0)
        text = reg.render()
        for family in ("fam_total", "fam_ms"):
            types = [ln for ln in text.splitlines()
                     if ln.startswith(f"# TYPE {family} ")]
            helps = [ln for ln in text.splitlines()
                     if ln.startswith(f"# HELP {family} ")]
            assert len(types) == 1, types
            assert len(helps) == 1, helps
        # 3 counter series under the single header
        assert text.count("fam_total{") == 3
        # 2 label sets x (2 buckets + +Inf) + _sum/_count per set
        assert text.count("fam_ms_bucket{") == 6
        assert text.count("fam_ms_sum{") == 2


class TestTraceRing:
    """Ring-buffer wraparound + concurrent flushes (PR-2 satellite:
    the previous tests only covered the happy path)."""

    def test_marks_wraparound_keeps_latest(self):
        rec = obst.TraceRecorder("wrap-marks", capacity=4)
        for i in range(100):
            rec.record_marks(i, (("a", float(i)), ("b", float(i) + 0.5)))
        events = rec.chrome_events()
        assert len(events) == 4            # one span per 2-mark frame
        assert sorted(e["args"]["frame"] for e in events) == [96, 97,
                                                              98, 99]

    def test_mixed_spans_and_marks_wraparound(self):
        rec = obst.TraceRecorder("wrap-mixed", capacity=3)
        for i in range(10):
            rec.record_span("s", float(i), 0.1, i)
            rec.record_marks(i, (("a", float(i)), ("b", float(i) + 1)))
        assert len(rec.chrome_events()) == 6   # 3 spans + 3 mark-frames
        rec.clear()
        assert len(rec) == 0 and rec.chrome_events() == []

    def test_concurrent_stage_timer_flushes(self):
        """N threads flushing StageTimers into one recorder while an
        exporter renders concurrently: no exception, bounded buffer,
        every surviving span belongs to a complete frame."""
        import threading

        rec = obst.TraceRecorder("conc", capacity=64)
        errors = []

        def writer(tid):
            try:
                for i in range(200):
                    st = StageTimer()
                    st.mark("capture")
                    st.mark("device-submit")
                    st.mark("publish")
                    st.flush_to(rec, obst.next_frame_id())
            except Exception as e:            # pragma: no cover
                errors.append(e)

        def exporter():
            try:
                for _ in range(50):
                    json.dumps(obst.export_chrome_trace([rec]))
            except Exception as e:            # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(4)] + [
                       threading.Thread(target=exporter)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        events = rec.chrome_events()
        assert 0 < len(events) <= 2 * 64       # 2 spans per 3-mark frame
        # spans arrive in frame pairs: every frame id appears twice
        from collections import Counter as C
        counts = C(e["args"]["frame"] for e in events)
        assert all(v == 2 for v in counts.values())

    def test_listener_sees_evicted_entries(self):
        """A listener (the budget ledger) must see every record even
        after the ring evicts it."""
        rec = obst.TraceRecorder("lst", capacity=2)
        got = []
        rec.add_listener(lambda kind, entry: got.append(kind))
        for i in range(10):
            rec.record_span("s", 0.0, 0.1, i)
        rec.record_marks(1, (("a", 0.0), ("b", 0.1)))
        assert got.count("span") == 10 and got.count("marks") == 1
        rec.remove_listener(got.append)        # unknown fn: no-op


# ---------------------------------------------------------------------------
# Glass-to-glass frame journeys (obs/journey, ISSUE 13)
# ---------------------------------------------------------------------------

class TestJourneyBook:
    def _book(self, name):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        return obsj.JourneyBook(name)

    def test_mint_complete_close_lifecycle(self):
        import time
        b = self._book("jb-life")
        try:
            t0 = time.perf_counter()
            b.mint(1, pts=9000, t_capture=t0)
            b.complete(1, t0 + 0.010, device_ms=4.0)
            assert b.close(1, t0 + 0.015, method="client")
            assert not b.close(1, t0 + 0.020)     # duplicate ignored
            assert not b.close(999)               # unknown id ignored
            s = b.summary()
            assert s["closed"] == 1 and s["open"] == 0
            assert s["by_method"] == {"client": 1}
            assert abs(s["p50_ms"] - 15.0) < 1.0
            assert abs(s["delivery_p50_ms"] - 5.0) < 1.0
        finally:
            b.close_book()

    def test_close_by_pts_rtcp_method(self):
        import time
        b = self._book("jb-pts")
        try:
            t0 = time.perf_counter()
            b.mint(7, pts=123456, t_capture=t0)
            b.complete(7, t0 + 0.005)
            assert b.close_by_pts(123456, t0 + 0.012, method="rtcp")
            assert not b.close_by_pts(999999)     # unknown pts
            assert b.summary()["by_method"] == {"rtcp": 1}
        finally:
            b.close_book()

    def test_chunk_amortization_is_honest(self):
        """Under the super-step ring the chunk frame pays the whole
        dispatch and staged frames pay ~0; the amortized view spreads
        the chunk total evenly — per-frame device spans stop lying."""
        import time
        b = self._book("jb-chunk")
        try:
            t0 = time.perf_counter()
            # chunk of 4: slot 0 carries 20 ms, slots 1-3 carry ~0
            for slot, dev in enumerate((20.0, 0.1, 0.1, 0.1)):
                fid = 10 + slot
                b.mint(fid, pts=fid * 1000, t_capture=t0)
                b.complete(fid, t0 + 0.01, device_ms=dev,
                           meta={"chunk_id": 5, "slot": slot,
                                 "chunk_len": 4, "shards": 2})
            rec = b.recent(4)
            assert all(abs(r["amortized_device_ms"] - 20.3 / 4) < 0.01
                       for r in rec), rec
            assert all(r["chunk_id"] == 5 and r["shards"] == 2
                       for r in rec)
        finally:
            b.close_book()

    def test_chunk_flush_boundary_keeps_per_frame_attribution(self):
        """Frames flushed through the per-frame path (partial ring at
        an IDR/idle drain) are UNCHUNKED: their device span is their
        own, not an amortized share of a chunk that never dispatched."""
        import time
        b = self._book("jb-flush")
        try:
            t0 = time.perf_counter()
            b.mint(50, t_capture=t0)
            b.complete(50, t0 + 0.01, device_ms=7.5,
                       meta={"chunk_id": None, "slot": 1,
                             "chunk_len": 1, "shards": 1})
            r = b.recent(1)[0]
            assert "chunk_id" not in r            # unchunked export
            assert r["amortized_device_ms"] == 7.5
        finally:
            b.close_book()

    def test_ring_bound_and_expiry_counter(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        b = obsj.JourneyBook("jb-ring", capacity=8)
        try:
            for fid in range(1, 20):
                b.mint(fid)
            assert len(b.recent(100)) <= 8
            assert b._m_expired.value >= 11       # evicted unclosed
            assert b.frontier() == 19
        finally:
            b.close_book()

    def test_frontier_and_global_summary(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        b = self._book("jb-front")
        try:
            b.mint(41)
            assert obsj.frontier().get("jb-front") == 41
            assert "jb-front" in obsj.global_summary()
        finally:
            b.close_book()
        assert "jb-front" not in obsj.frontier()

    def test_probe_sampling_knob(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        keep = obsj.sample_every()
        try:
            obsj.sample_every(4)
            assert obsj.probe_due(8) and not obsj.probe_due(9)
            obsj.sample_every(0)
            assert not obsj.probe_due(8)          # RTCP-only mode
        finally:
            obsj.sample_every(keep)

    def test_disabled_switch_is_total(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        b = self._book("jb-off")
        try:
            obsj.set_enabled(False)
            assert b.mint(1) is None
            assert not b.close(1)
            assert not obsj.probe_due(8)
        finally:
            obsj.set_enabled(True)
            b.close_book()

    def test_close_feeds_delivery_stage(self):
        """Journey closure lands the delivery stage in the budget
        ledger — distinct from compute stages and from link-RTT."""
        import time

        from docker_nvidia_glx_desktop_tpu.obs import budget as obsb
        b = self._book("jb-del")
        try:
            n0 = len(obsb.LEDGER._stages.get("delivery", ()))
            t0 = time.perf_counter()
            b.mint(3, t_capture=t0)
            b.complete(3, t0 + 0.004)
            b.close(3, t0 + 0.010)
            dq = obsb.LEDGER._stages.get("delivery")
            assert dq is not None and len(dq) == n0 + 1
            # free-standing: must NOT join the compute-floor clamp
            assert "delivery" not in obsb.LEDGER._frame_stages
        finally:
            b.close_book()

    def test_close_book_removes_label_series(self):
        b = self._book("jb-gone")
        b.mint(1)
        b.close_book()
        text = obsm.REGISTRY.render()
        assert 'session="jb-gone"' not in text


class TestTraceDropLoss:
    """Silent trace loss is a counter, never invisible (ISSUE 13)."""

    def test_ring_overwrite_counts(self):
        d0 = obst.dropped_total()
        rec = obst.TraceRecorder("drop-ring", capacity=4)
        for i in range(10):
            rec.record_span("s", 0.0, 0.1, i)
        assert obst.dropped_total() - d0 == 6
        assert rec._m_overwrite.value == 6

    def test_raising_listener_counted_not_propagated(self):
        rec = obst.TraceRecorder("drop-lst")

        def bad(kind, entry):
            raise RuntimeError("listener bug")

        rec.add_listener(bad)
        rec.record_span("s", 0.0, 0.1, 1)          # must not raise
        rec.record_marks(1, (("a", 0.0), ("b", 0.1)))
        assert rec._m_listener.value == 2

    def test_dropped_metric_on_exposition(self):
        rec = obst.TraceRecorder("drop-exp", capacity=1)
        rec.record_span("s", 0.0, 0.1, 1)
        rec.record_span("s", 0.0, 0.1, 2)
        text = obsm.REGISTRY.render()
        assert ('dngd_trace_dropped_total{tracer="drop-exp",'
                'reason="ring_overwrite"}') in text


class TestChromeExportLanes:
    """/debug/trace: chunk/shard args + per-session track lanes."""

    def test_meta_lands_in_args(self):
        rec = obst.TraceRecorder("lane-args")
        rec.record_marks(4, (("a", 0.0), ("b", 0.1)), pts=9000,
                         meta=(("chunk", 3), ("slot", 1), ("shards", 4)))
        ev = [e for e in rec.chrome_events() if e["ph"] == "X"][0]
        assert ev["args"]["chunk"] == 3
        assert ev["args"]["slot"] == 1
        assert ev["args"]["shards"] == 4

    def test_per_session_lanes(self):
        """Two sessions' spans on one recorder export as two named
        tracks, not one interleaved blob."""
        rec = obst.TraceRecorder("lane-sess")
        rec.record_marks(1, (("a", 0.0), ("b", 0.1)),
                         meta=(("session", "s0"),))
        rec.record_marks(2, (("a", 0.2), ("b", 0.3)),
                         meta=(("session", "s1"),))
        rec.record_span("free", 0.4, 0.1, 3)       # no meta: base lane
        doc = obst.export_chrome_trace([rec])
        names = {e["args"]["name"]: e["tid"]
                 for e in doc["traceEvents"] if e["ph"] == "M"}
        assert "lane-sess:s0" in names and "lane-sess:s1" in names
        assert names["lane-sess:s0"] != names["lane-sess:s1"]
        xs = {e["args"].get("session"): e["tid"]
              for e in doc["traceEvents"] if e["ph"] == "X"}
        assert xs["s0"] == names["lane-sess:s0"]
        assert xs["s1"] == names["lane-sess:s1"]
        assert xs[None] == names["lane-sess"]      # base recorder lane


class TestEventTimeline:
    def test_emit_anchors_frame_frontier(self):
        from docker_nvidia_glx_desktop_tpu.obs import events as obsev
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        b = obsj.JourneyBook("ev-anchor")
        try:
            b.mint(77)
            ev = obsev.emit("degrade", session="ev-anchor", step="qp")
            assert ev["frontier"].get("ev-anchor") == 77
            assert ev["kind"] == "degrade" and ev["step"] == "qp"
        finally:
            b.close_book()

    def test_ring_bounded_and_snapshot(self):
        from docker_nvidia_glx_desktop_tpu.obs import events as obsev
        log = obsev.EventLog(capacity=8)
        for i in range(20):
            log.emit("admit", session=f"s{i}")
        snap = log.snapshot()
        assert snap["count"] == 8 and snap["capacity"] == 8
        assert snap["by_kind"] == {"admit": 8}
        text = obsev.render_events_text(log)
        assert "admit" in text and "s19" in text

    def test_listener_exceptions_swallowed(self):
        from docker_nvidia_glx_desktop_tpu.obs import events as obsev
        log = obsev.EventLog()
        log.add_listener(lambda ev: 1 / 0)
        log.emit("shed")                           # must not raise
        assert len(log) == 1


class TestFlightRecorder:
    def test_fault_fire_triggers_dump_with_payload(self):
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        from docker_nvidia_glx_desktop_tpu.resilience import faults as rf
        b = obsj.JourneyBook("fl-pay")
        obsf.FLIGHT.clear()
        try:
            b.mint(5)
            rf.arm("collect_timeout", count=1)
            rf.fire("collect_timeout")
            dump = obsf.FLIGHT.find_dump("fault-fire", "collect_timeout")
            assert dump is not None
            assert dump["journeys"]["fl-pay"], dump["journeys"]
            assert any(e["kind"] == "fault-fire"
                       and e.get("point") == "collect_timeout"
                       for e in dump["events"])
            assert "stages" in dump["budget"]
            assert obsf.FLIGHT.by_reason()[
                "fault-fire:collect_timeout"] == 1
        finally:
            rf.disarm_all()
            obsf.FLIGHT.clear()
            b.close_book()

    def test_debounce_per_reason(self):
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        fr = obsf.FlightRecorder(min_interval_s=60.0)
        fr.on_event({"kind": "shed", "session": "a"})
        fr.on_event({"kind": "shed", "session": "a"})   # debounced
        fr.on_event({"kind": "shed", "session": "b"})   # distinct name
        fr.on_event({"kind": "admit"})                  # not a trigger
        assert len(fr.dumps()) == 2

    def test_state_provider_embedded(self):
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        fr = obsf.FlightRecorder()
        fr.register_state_provider("fleet", lambda: {"active": 3})
        snap = fr.dump("mesh-rebuild", "2x2")
        assert snap["fleet"] == {"active": 3}
        assert fr.snapshot()["index"][0]["kind"] == "mesh-rebuild"

    def test_spool_written_and_capped(self, tmp_path, monkeypatch):
        import json as _json
        import os

        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        monkeypatch.setenv("DNGD_FLIGHT_SPOOL", str(tmp_path))
        monkeypatch.setattr(obsf, "SPOOL_MAX_FILES", 3)
        fr = obsf.FlightRecorder(min_interval_s=0.0)
        for i in range(5):
            fr.dump("breaker-open", f"p{i}")
        fr.flush_spool()
        names = sorted(os.listdir(tmp_path))
        assert 0 < len(names) <= 3
        with open(tmp_path / names[-1]) as f:
            doc = _json.load(f)
        assert doc["kind"] == "breaker-open"
        assert "budget" in doc and "events" in doc

    def test_no_spool_dir_means_memory_only(self, monkeypatch):
        from docker_nvidia_glx_desktop_tpu.obs import flight as obsf
        monkeypatch.delenv("DNGD_FLIGHT_SPOOL", raising=False)
        fr = obsf.FlightRecorder()
        fr.dump("shed", "x")
        assert fr.spool_dir() is None and len(fr.dumps()) == 1


class TestRtcpJourneyHook:
    def test_monitor_on_block_fires_with_kind_and_rtt(self):
        got = []
        mon = rtcp.PeerRtcpMonitor({10: ("video", 90_000),
                                    20: ("audio", 48_000)})
        mon.on_block = lambda kind, blk, rtt: got.append((kind, blk))
        rr = rtcp.receiver_report(99, [
            {"ssrc": 10, "highest_seq": 1234, "jitter": 90}])
        mon.ingest(rr)
        mon.close()
        assert got and got[0][0] == "video"
        assert got[0][1]["highest_seq"] == 1234

    def test_raising_hook_does_not_break_ingest(self):
        mon = rtcp.PeerRtcpMonitor({10: ("video", 90_000)})
        mon.on_block = lambda *a: 1 / 0
        rr = rtcp.receiver_report(99, [{"ssrc": 10, "highest_seq": 5}])
        assert mon.ingest(rr) == 1                 # still counted
        mon.close()


class TestJourneyEndToEndWs:
    """The /ws path end to end without JAX: fprobe goes out with a
    sampled frame's fragment, the client's ack closes the journey."""

    def test_fprobe_ack_closes_journey(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        from docker_nvidia_glx_desktop_tpu.web.session import SubscriberSet

        class AckSession:
            codec_name = "h264_cavlc"

            class source:
                width, height = 64, 48

            def __init__(self):
                self.init_segment = b"INIT"
                self._subs = SubscriberSet()
                self.journeys = obsj.JourneyBook("ws-ack")

            def hello(self):
                return {"type": "hello", "codec": self.codec_name,
                        "mime": 'video/mp4; codecs="avc1.42E01E"',
                        "width": 64, "height": 48}

            def subscribe(self, maxsize=8):
                return self._subs.subscribe(
                    [("init", self.init_segment)], maxsize=maxsize)

            def unsubscribe(self, q):
                self._subs.unsubscribe(q)

            def request_keyframe(self):
                pass

        async def scenario():
            import time

            from docker_nvidia_glx_desktop_tpu.obs import journey as obsj

            keep = obsj.sample_every()
            obsj.sample_every(1)                 # probe every frame
            cfg = from_env({"ENABLE_BASIC_AUTH": "false",
                            "LISTEN_ADDR": "127.0.0.1",
                            "LISTEN_PORT": "0"})
            sess = AckSession()
            runner = await serve(cfg, session=None, injector=None)
            # mount with a session double: use make_app directly
            await runner.cleanup()
            from docker_nvidia_glx_desktop_tpu.web.server import make_app
            from aiohttp import web as aioweb
            runner = aioweb.AppRunner(make_app(cfg, sess, injector=None))
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            try:
                port = bound_port(runner)
                async with ClientSession() as http:
                    async with http.ws_connect(
                            f"http://127.0.0.1:{port}/ws") as ws:
                        hello = await ws.receive_json()
                        assert hello["type"] == "hello"
                        # a published frame journeys through: mint +
                        # complete on the "encode" side, frag carries fid
                        fid = 424242
                        t0 = time.perf_counter()
                        sess.journeys.mint(fid, t_capture=t0)
                        sess.journeys.complete(fid, t0 + 0.001)
                        sess._subs.publish(("frag", b"AU", True, fid),
                                           keyframe=True)
                        # init (binary), then fprobe (text), then frag
                        seen_probe = False
                        for _ in range(4):
                            msg = await ws.receive(timeout=10)
                            if msg.type == WSMsgType.TEXT:
                                ctrl = json.loads(msg.data)
                                if ctrl.get("type") == "fprobe":
                                    assert ctrl["id"] == fid
                                    seen_probe = True
                                    await ws.send_json(
                                        {"type": "ack", "id": fid})
                            elif (msg.type == WSMsgType.BINARY
                                    and msg.data == b"AU"):
                                if seen_probe:
                                    break
                        assert seen_probe
                        # the ack lands on the server loop; poll summary
                        for _ in range(50):
                            if sess.journeys.summary()["closed"]:
                                break
                            await asyncio.sleep(0.05)
                s = sess.journeys.summary()
                assert s["closed"] == 1
                assert s["by_method"] == {"client": 1}
            finally:
                obsj.sample_every(keep)
                sess.journeys.close_book()
                await runner.cleanup()

        run(scenario())


class TestObsDebugEndpoints:
    """/debug/events and /debug/flight are mounted, auth-exempt, and
    serve text/JSON like the other telemetry routes."""

    def test_events_and_flight_routes(self):
        from docker_nvidia_glx_desktop_tpu.obs import events as obsev

        async def scenario():
            cfg = from_env({"ENABLE_BASIC_AUTH": "true",
                            "BASIC_AUTH_PASSWORD": "pw",
                            "LISTEN_ADDR": "127.0.0.1",
                            "LISTEN_PORT": "0"})
            runner = await serve(cfg)
            try:
                port = bound_port(runner)
                obsev.emit("degrade", session="ep", step="qp_up")
                async with ClientSession() as http:
                    # auth-exempt (no credentials on purpose)
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/events"
                            "?format=json") as r:
                        assert r.status == 200
                        doc = await r.json()
                        assert any(e["kind"] == "degrade"
                                   and e.get("session") == "ep"
                                   for e in doc["events"])
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/events"
                            ) as r:
                        assert r.status == 200
                        assert "degrade" in await r.text()
                    async with http.get(
                            f"http://127.0.0.1:{port}/debug/flight"
                            ) as r:
                        assert r.status == 200
                        doc = await r.json()
                        assert "dumps" in doc and "by_reason" in doc
            finally:
                await runner.cleanup()

        run(scenario())


class TestStatsChannelAck:
    """The stock-selkies stats data channel doubles as the ack path:
    {"type": "ack", "frame_id": N} closes the frame's journey; any
    other message still gets the HUD stats reply."""

    def test_ack_closes_journey_and_stats_still_replies(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        from docker_nvidia_glx_desktop_tpu.web.selkies_shim import (
            attach_input_channels)

        class FakeChannel:
            label = "stats"
            on_message = None
            sent = []

            def send(self, data):
                self.sent.append(data)

        class FakePeer:
            close_hooks = []
            on_datachannel = None

        class FakeSession:
            journeys = obsj.JourneyBook("dc-ack")

            def stats_summary(self):
                return {"fps": 1.0}

        sess = FakeSession()
        try:
            peer = FakePeer()
            attach_input_channels(peer, sess, injector=None)
            ch = FakeChannel()
            peer.on_datachannel(ch)
            sess.journeys.mint(9)
            sess.journeys.complete(9, __import__("time").perf_counter())
            ch.on_message(json.dumps({"type": "ack", "frame_id": 9}))
            assert sess.journeys.summary()["closed"] == 1
            assert sess.journeys.summary()["by_method"] == {"client": 1}
            assert not ch.sent                 # acks get no reply
            ch.on_message("hud poll")
            assert ch.sent and '"stats"' in ch.sent[0]
        finally:
            sess.journeys.close_book()


class TestJourneyGaugeAndLossHonesty:
    def test_open_gauge_counts_open_not_ring_occupancy(self):
        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        b = obsj.JourneyBook("jb-open")
        try:
            import time
            t0 = time.perf_counter()
            for fid in (1, 2, 3):
                b.mint(fid, t_capture=t0)
                b.complete(fid, t0)
            b.close(1)
            b.close(2)
            # closed journeys stay ringed (flight recorder) but are
            # NOT open
            assert len(b.recent(10)) == 3
            assert b._open_count() == 1.0
        finally:
            b.close_book()

    def test_rtcp_lossy_interval_retires_without_closing(self):
        """A report block with fraction_lost > 0 cannot prove any
        covered frame arrived complete: the peer must retire those
        frames unclosed (they expire, not count as delivered)."""
        import time

        from docker_nvidia_glx_desktop_tpu.obs import journey as obsj
        try:
            # peer -> dtls dlopens libssl.so.3 at import; dev images
            # without OpenSSL 3 skip (CI runners ship it and run this)
            from docker_nvidia_glx_desktop_tpu.webrtc.peer import (
                WebRtcPeer)
        except OSError as e:
            pytest.skip(f"system libssl unavailable: {e}")

        from types import SimpleNamespace

        from docker_nvidia_glx_desktop_tpu.webrtc.feedback import (
            FrameSeqLog)

        b = obsj.JourneyBook("rr-loss")
        try:
            t0 = time.perf_counter()
            for fid, pts in ((1, 1000), (2, 2000)):
                b.mint(fid, pts=pts, t_capture=t0)
                b.complete(fid, t0)
            # drive the unbound method on a stub (constructing a real
            # peer needs libssl): only the attrs _on_rr_block touches
            stub = type("S", (), {})()
            stub.journeys = b
            stub._frame_log = FrameSeqLog(100)
            stub._frame_log.note_frame(3, 1000)
            stub._frame_log.note_frame(6, 2000)
            stub.video = SimpleNamespace(packet_count=6)
            rr = WebRtcPeer._on_rr_block
            # lossy interval covering frame 1: retired, NOT closed
            rr(stub, "video", {"highest_seq": 102, "fraction_lost": 25},
               None)
            assert b.summary()["closed"] == 0
            assert len(stub._frame_log) == 1
            # clean interval covering frame 2: closed via rtcp
            rr(stub, "video", {"highest_seq": 105, "fraction_lost": 0},
               2.0)
            assert b.summary()["by_method"] == {"rtcp": 1}
            assert not len(stub._frame_log)
        finally:
            b.close_book()
