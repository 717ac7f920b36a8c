"""Zero-downtime handoff tests (ISSUE 19), fast tier.

Covers the migration plane piece by piece: the self-describing
snapshot envelope (schema-stamped, tagged-JSON — never pickle across
the trust boundary), the HandoffManager broker on both sides of a
restart (export / spool / import / single-use TTL-bounded claim), the
wire-continuity exports (RTP sequence frontier, SCTP TSN/SSN
geometry, SRTP rollover counters), the encoder checkpoint schema pin
(forward-compat: a future schema bump must be REJECTED, not
half-imported), and the fleet scheduler's migration admission + the
reason-labeled shed split.

The end-to-end two-process migration rides tests/test_handoff_smoke.py
(slow tier / the CI handoff-smoke step) and the chaos bench's
``rolling_restart`` scenario.
"""

import asyncio
import struct

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.fleet.capacity import CapacityModel
from docker_nvidia_glx_desktop_tpu.fleet.scheduler import (
    Admission, FleetScheduler)
from docker_nvidia_glx_desktop_tpu.models.base import (
    CKPT_SCHEMA, CheckpointSchemaError)
from docker_nvidia_glx_desktop_tpu.resilience import handoff
from docker_nvidia_glx_desktop_tpu.resilience.handoff import (
    HANDOFF_SCHEMA, HandoffManager, HandoffSchemaError, decode_snapshot,
    encode_snapshot)
from docker_nvidia_glx_desktop_tpu.webrtc.rtp import RtpStream, parse_header
from docker_nvidia_glx_desktop_tpu.webrtc.sctp import SctpAssociation


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 30))
    finally:
        loop.close()


# -- snapshot envelope ----------------------------------------------------

class TestSnapshotCodec:
    def test_schema_is_pinned(self):
        # forward-compat contract: bumping the schema is an explicit
        # decision that must come with migration logic, not a drive-by
        assert HANDOFF_SCHEMA == 1

    def test_roundtrip_preserves_rich_types(self):
        snap = {
            "sessions": [{"index": 0, "state": {
                "ref": (np.arange(6, dtype=np.float32).reshape(2, 3),
                        np.zeros((1, 2), dtype=np.uint8)),
                "frame_index": 42,
                "blob": b"\x00\x01\xff",
            }}],
            "conns": [{"token": "t", "sid": "s", "tier": 3,
                       "wire": {"video": {"ssrc": 0xDEADBEEF,
                                          "seq": 65534}}}],
        }
        back = decode_snapshot(encode_snapshot(snap))
        st = back["sessions"][0]["state"]
        assert isinstance(st["ref"], tuple) and len(st["ref"]) == 2
        assert st["ref"][0].dtype == np.float32
        assert st["ref"][0].shape == (2, 3)
        assert np.array_equal(
            st["ref"][0], np.arange(6, dtype=np.float32).reshape(2, 3))
        assert st["blob"] == b"\x00\x01\xff"
        wire = back["conns"][0]["wire"]["video"]
        assert wire == {"ssrc": 0xDEADBEEF, "seq": 65534}

    def test_envelope_is_self_describing(self):
        import json
        env = json.loads(encode_snapshot({"sessions": [], "conns": []}))
        assert env["schema"] == HANDOFF_SCHEMA
        assert "created" in env and "pid" in env

    def test_schema_mismatch_rejected_clearly(self):
        import json
        env = json.loads(encode_snapshot({"sessions": [], "conns": []}))
        env["schema"] = HANDOFF_SCHEMA + 1
        with pytest.raises(HandoffSchemaError) as ei:
            decode_snapshot(json.dumps(env).encode())
        assert "schema" in str(ei.value)

    def test_garbage_rejected_not_crashed(self):
        with pytest.raises(handoff.HandoffError):
            decode_snapshot(b"\x80\x04not json at all")


# -- encoder checkpoint schema (satellite: export_state version stamp) ----

class TestCheckpointSchema:
    def test_schema_is_pinned(self):
        assert CKPT_SCHEMA == 1

    def _enc(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        return H264Encoder(128, 96, gop=10)

    def test_export_carries_schema_and_codec_id(self):
        st = self._enc().export_state()
        assert st["schema"] == CKPT_SCHEMA
        assert st["codec"] == "h264"
        assert {"width", "height", "frame_index"} <= set(st)

    def test_future_schema_rejected(self):
        enc, enc2 = self._enc(), self._enc()
        st = enc.export_state()
        st["schema"] = CKPT_SCHEMA + 1
        with pytest.raises(CheckpointSchemaError) as ei:
            enc2.import_state(st)
        assert "schema" in str(ei.value)

    def test_codec_mismatch_rejected(self):
        enc, enc2 = self._enc(), self._enc()
        st = enc.export_state()
        st["codec"] = "vp8"
        with pytest.raises(CheckpointSchemaError):
            enc2.import_state(st)

    def test_schema_error_is_a_valueerror(self):
        # compat pin: pre-existing callers catch ValueError on geometry
        # mismatch (tests/test_resilience.py) — the subclassing is API
        assert issubclass(CheckpointSchemaError, ValueError)


# -- the broker -----------------------------------------------------------

class _StubSession:
    def __init__(self, state=None, boom=False):
        self._state = state if state is not None else {"frame_index": 7}
        self._boom = boom

    def export_handoff(self):
        if self._boom:
            raise RuntimeError("encoder walked off")
        return dict(self._state)


class TestHandoffManager:
    def test_disabled_without_destination(self):
        assert not HandoffManager().enabled
        assert HandoffManager(handoff_dir="/tmp/x").enabled
        assert HandoffManager(sock_path="/tmp/x.sock").enabled

    def test_export_sessions_and_wires(self):
        m = HandoffManager(handoff_dir="unused")
        tok = m.register("sid-1", tier=2)
        m.attach_wire(tok, lambda: {"video": {"ssrc": 1, "seq": 9}})
        m.register("sid-2")                      # MSE-only: no wire
        snap = m.export([_StubSession({"frame_index": 3})])
        assert snap["sessions"] == [
            {"index": 0, "state": {"frame_index": 3}}]
        by_sid = {c["sid"]: c for c in snap["conns"]}
        assert by_sid["sid-1"]["tier"] == 2
        assert by_sid["sid-1"]["wire"]["video"]["seq"] == 9
        assert by_sid["sid-2"]["wire"] is None

    def test_bad_session_dropped_not_fatal(self):
        m = HandoffManager(handoff_dir="unused")
        snap = m.export([_StubSession(boom=True),
                         _StubSession({"frame_index": 1})])
        assert [s["index"] for s in snap["sessions"]] == [1]
        assert m.failures == 1

    def test_bad_wire_drops_only_that_conn(self):
        m = HandoffManager(handoff_dir="unused")

        def _boom():
            raise RuntimeError("peer gone")

        t1 = m.register("bad")
        m.attach_wire(t1, _boom)
        m.register("good")
        snap = m.export([])
        assert [c["sid"] for c in snap["conns"]] == ["good"]

    def test_claim_is_single_use(self):
        m = HandoffManager(handoff_dir="unused")
        tok = m.register("sid", tier=1)
        snap = m.export([])
        m2 = HandoffManager(handoff_dir="unused")
        m2.import_snapshot(snap)
        entry = m2.claim(tok)
        assert entry is not None and entry["tier"] == 1
        assert m2.claim(tok) is None, "resume tokens are single-use"

    def test_claim_expires_on_ttl(self):
        now = [0.0]
        m = HandoffManager(handoff_dir="unused", token_ttl_s=10.0,
                           clock=lambda: now[0])
        m.import_snapshot({"sessions": [],
                           "conns": [{"token": "tk", "sid": "s",
                                      "tier": 0, "wire": None}]})
        now[0] = 11.0
        assert m.claim("tk") is None, "an expired token must not resume"

    def test_detach_removes_from_migration_set(self):
        m = HandoffManager(handoff_dir="unused")
        tok = m.register("sid")
        assert m.live_count() == 1
        m.detach(tok)
        assert m.live_count() == 0
        assert m.export([])["conns"] == []

    def test_notify_all_hands_out_tokens(self):
        m = HandoffManager(handoff_dir="unused")
        got = []
        tok = m.register("sid",
                         notify=lambda t, r: got.append((t, r)))
        m.register("silent")                     # no notify callback
        assert m.notify_all(retry_after_s=0.5) == 1
        assert got == [(tok, 0.5)]

    def test_spool_roundtrip(self, tmp_path):
        a = HandoffManager(handoff_dir=str(tmp_path))
        tok = a.register("sid", tier=4)
        a.attach_wire(tok, lambda: {"video": {"ssrc": 5, "seq": 100}})
        path = a.spool(a.export([_StubSession({"frame_index": 9})]))
        assert path.endswith(".json")

        b = HandoffManager(handoff_dir=str(tmp_path))
        sessions = b.load_spool()
        assert sessions[0]["state"]["frame_index"] == 9
        entry = b.claim(tok)
        assert entry["wire"]["video"]["seq"] == 100
        # the spool is consumed: a third process must never replay it
        assert b.load_spool() == []

    def test_spool_schema_reject_consumes_file(self, tmp_path):
        import json
        bad = {"schema": HANDOFF_SCHEMA + 1, "snapshot": {}}
        (tmp_path / "handoff-999.json").write_text(json.dumps(bad))
        m = HandoffManager(handoff_dir=str(tmp_path))
        assert m.load_spool() == []
        assert m.failures == 1
        assert list(tmp_path.glob("handoff-*.json")) == [], \
            "a rejected spool file must still be consumed"


# -- wire continuity ------------------------------------------------------

class TestWireContinuity:
    def test_rtp_stream_seq_frontier_survives(self):
        a = RtpStream(96)
        pkts = a.packetize([b"x"] * 3, timestamp=1000)
        last = parse_header(pkts[-1])

        b = RtpStream(96)
        b.import_state(a.export_state())
        nxt = parse_header(b.packetize([b"y"], timestamp=2000)[0])
        assert nxt["ssrc"] == last["ssrc"], "SSRC must survive handoff"
        assert nxt["seq"] == (last["seq"] + 1) & 0xFFFF, \
            "successor's first packet continues the sequence space"

    def test_rtp_export_masks_counters(self):
        a = RtpStream(96)
        st = a.export_state()
        st["seq"] = 0x1FFFF                      # hostile/corrupt spool
        b = RtpStream(96)
        b.import_state(st)
        assert parse_header(
            b.packetize([b"z"], timestamp=0)[0])["seq"] == 0xFFFF

    def test_sctp_tsn_geometry_seeds_pre_handshake(self):
        a = SctpAssociation(role="server")
        st = a.export_state()
        b = SctpAssociation(role="server")
        b.import_state(st)
        assert b._next_tsn == a._next_tsn
        # the INIT advertises the imported initial TSN — without this
        # the peer's cumulative-ack base and ours diverge immediately
        assert b._initial_out_tsn == b._next_tsn

    def test_sctp_ssn_maps_roundtrip_int_keys(self):
        a = SctpAssociation(role="server")
        a._ssn_out[1] = 41
        a._next_ssn_in[2] = 17
        # through the JSON envelope (keys become strings on the wire)
        snap = decode_snapshot(encode_snapshot(a.export_state()))
        b = SctpAssociation(role="server")
        b.import_state(snap)
        assert b._ssn_out == {1: 41}
        assert b._next_ssn_in == {2: 17}


# -- SRTP ROC continuity across handoff (satellite: PR 14 per-SSRC rig) --

class TestSrtpRocHandoff:
    # RFC 3711 appendix B.3 key-derivation test vectors — session keys
    # re-derive on the successor from the SAME DTLS association inputs;
    # only the rollover GEOMETRY crosses the process boundary.
    MK = bytes.fromhex("E1F97A0D3E018BE0D64FA32C06DE4139")
    MS = bytes.fromhex("0EC675AD498AFEEBB6960B3AABE6")

    @staticmethod
    def _spkt(ssrc, seq, payload=b"x" * 32):
        return struct.pack(">BBHII", 0x80, 96, seq, 1000 + seq,
                           ssrc) + payload

    def _ctx(self):
        pytest.importorskip("cryptography")
        from docker_nvidia_glx_desktop_tpu.webrtc.srtp import SrtpContext
        return SrtpContext(self.MK, self.MS)

    def test_roc_survives_handoff_and_rtx_decrypts(self):
        """A NACK-answered RTX for a PRE-handoff sequence number must
        decrypt on the successor: the packet index is (ROC << 16) | seq,
        so losing the rollover counter across the restart would make
        every post-wrap packet fail authentication silently."""
        tx, rx = self._ctx(), self._ctx()
        # the video stream wraps its 16-bit space pre-handoff...
        for seq in [65533, 65534, 65535, 0, 1, 2]:
            p = self._spkt(0xA, seq)
            assert rx.unprotect(tx.protect(p)) == p
        assert tx._send_ext[0xA] >> 16 == 1      # era 1 on the sender
        wire_tx = tx.export_rollover_state()
        wire_rx = rx.export_rollover_state()

        # successor: fresh contexts (fresh DTLS => same test keys),
        # rollover geometry imported from the handoff snapshot
        tx2, rx2 = self._ctx(), self._ctx()
        tx2.import_rollover_state(wire_tx)
        rx2.import_rollover_state(wire_rx)
        assert tx2._send_ext[0xA] >> 16 == 1

        # post-handoff media continues in era 1 without a glitch
        for seq in [3, 4]:
            p = self._spkt(0xA, seq)
            assert rx2.unprotect(tx2.protect(p)) == p
        # the RTX window the handoff must preserve: a verbatim resend
        # of a PRE-handoff, PRE-wrap seq resolves back into era 0
        late = self._spkt(0xA, 65534)
        assert rx2.unprotect(tx2.protect(late)) == late
        assert tx2._send_ext[0xA] >> 16 == 1, \
            "answering the NACK must not disturb the live frontier"

    def test_fresh_context_without_import_breaks(self):
        """The negative control: WITHOUT the rollover import, the
        successor authenticates the post-wrap stream in era 0 and the
        receiver must reject it — the exact outage handoff prevents."""
        tx, rx = self._ctx(), self._ctx()
        for seq in [65533, 65534, 65535, 0, 1, 2]:
            p = self._spkt(0xA, seq)
            rx.unprotect(tx.protect(p))
        tx2 = self._ctx()                        # no import: era 0
        with pytest.raises(ValueError):
            rx.unprotect(tx2.protect(self._spkt(0xA, 3)))

    def test_rollover_state_roundtrips_the_envelope(self):
        tx = self._ctx()
        for seq in [65535, 0]:
            tx.protect(self._spkt(0xA, seq))
        snap = decode_snapshot(
            encode_snapshot(tx.export_rollover_state()))
        tx2 = self._ctx()
        tx2.import_rollover_state(snap)
        assert tx2._send_ext == tx._send_ext


# -- fleet: migration admission + reason-labeled sheds --------------------

class TestFleetMigration:
    def _sched(self, **kw):
        kw.setdefault("model", CapacityModel(per_chip_override=1))
        kw.setdefault("chips_fn", lambda: 2)
        kw.setdefault("geometry", (128, 96))
        kw.setdefault("fps", 30.0)
        kw.setdefault("queue_depth", 2)
        kw.setdefault("queue_timeout_s", 0.2)
        kw.setdefault("retry_after_s", 1.0)
        return FleetScheduler(**kw)

    def test_admit_migration_bypasses_full_gate(self):
        async def go():
            s = self._sched()
            a = [await s.acquire() for _ in range(2)]
            assert all(x.admitted for x in a) and s.at_capacity
            # a migrating session must NOT queue behind fresh joiners
            adm = s.admit_migration(tier=3)
            assert isinstance(adm, Admission) and adm.admitted
            assert adm.tier == 3
            assert s.active == 3 and s.migrations == 1
            return s

        run(go())

    def test_account_drain_splits_reason_label(self):
        async def go():
            s = self._sched()
            await s.acquire()
            await s.acquire()
            assert s.account_drain("drain") == 2
            assert s.account_drain("handoff_failed") == 2
            assert s.sheds == 4
            return s

        run(go())
        from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY
        text = REGISTRY.render()
        assert 'dngd_fleet_shed_total{mode="evicted",reason="drain"}' \
            in text
        assert 'reason="handoff_failed"' in text

    def test_shed_metric_carries_both_labels(self):
        s = self._sched()
        s.count_shed("migrated", "overload", session="s1")
        s.count_shed("evicted", "chip_lost", session="s2")
        from docker_nvidia_glx_desktop_tpu.obs.metrics import REGISTRY
        text = REGISTRY.render()
        assert 'mode="migrated",reason="overload"' in text
        assert 'mode="evicted",reason="chip_lost"' in text
