"""Chaos-mode loopback bench: every registered fault point, recovered.

``bench.py --chaos`` drives the REAL serving path (SyntheticSource ->
StreamSession -> muxer -> aiohttp server, the same stack the loopback
serving-budget bench uses) and then injects every canonical failure
point from :mod:`..resilience.faults`, asserting per fault that the
session survives and the stream resumes (keyframe-bearing fragment
delivered after the last injected firing) within a bounded recovery
time.  Serving-path faults are injected against the live session;
``turn_refresh_401`` runs against a TURN allocation on a scripted
in-process responder (no coturn on the wire), and
``peer_rtcp_loss_burst`` plus the sustained-budget-breach scenario
drive the live :class:`..resilience.degrade.DegradeController` ladder
— downshift under breach, restore after, transitions visible on
``/metrics``.

The report is the ``chaos`` block bench emits: per-fault
``{fired, recovered, recovery_ms}`` plus the degradation scenario's
level trajectory.  The data-channel scenarios (ISSUE 11) ride a
packet-level SCTP loopback: ``sctp_drop_burst`` swallows packets
mid-typing and asserts retransmission redelivers every keystroke in
order to the X input backend; ``dcep_open_stall`` delays the
DATA_CHANNEL_ACK and asserts the deferred flush completes the open.

The RTCP feedback scenarios (ISSUE 14) ride the seeded impairment
shim (web/impair) against the real packet machinery
(webrtc/feedback): ``rtp_loss_burst`` tail-drops 4 media packets
mid-stream and asserts NACK/RTX repairs them with contiguous frames
and NO keyframe spent; ``pli_storm`` asserts the session's
rate-limited ``request_idr`` collapses a burst of PLIs into exactly
one granted IDR; the ``remb_cap`` scenario caps the link's bandwidth
and asserts the ladder walks down on the REMB headroom signal alone
and restores when the cap lifts.

The quality-plane scenario (ISSUE 17) parks the content plane's PSNR
floor above any achievable fidelity and asserts the resulting
``psnr_floor_breach`` event reaches ``/debug/events`` and that the
flight recorder's triggered dump embeds the content-state block.

Session-continuity scenarios (ISSUE 4) ride the same harness:
``device_preempt`` preempts the device mid-GOP and asserts the session
recovers on a restored device with the SAME SSRC, contiguous RTP
sequence numbers (observed through a peer-equivalent RTP tap on the AU
listener path — the exact packetizer state a live WebRTC peer carries
across recovery) and a bounded frame gap; ``mesh_chip_lost`` drops one
chip of a live multi-session mesh and asserts the survivors re-bucket
and every session resumes from its recovery IDR.

The rolling-restart scenario (ISSUE 19) retires a whole process
generation: a drain on the predecessor MIGRATES (encoder lineage +
per-connection wire continuity spooled through ``DNGD_HANDOFF_DIR``),
the successor adopts the snapshot before its first frame, and the
client redeems its resume token seeing the same SSRC, contiguous RTP
sequence numbers, exactly one recovery IDR and zero sheds — the
acceptance contract for zero-downtime restarts.
"""

from __future__ import annotations

import asyncio
import json
import logging
import struct
import time
from typing import Optional

from ..resilience import faults as rfaults
from ..resilience.degrade import DegradeController, SessionExecutor
from ..utils.config import Config
from .loopback import serving_budget_config

log = logging.getLogger(__name__)

__all__ = ["run_chaos"]


async def _await_frag(frags, after_t: float, deadline_s: float,
                      require_key: bool = False) -> Optional[float]:
    """Wait until the in-process sink logged a (keyframe-bearing, when
    ``require_key``) fragment newer than ``after_t``; returns its
    timestamp or None on timeout."""
    deadline = time.perf_counter() + deadline_s
    while time.perf_counter() < deadline:
        for t, key in reversed(frags):
            if t > after_t and (key or not require_key):
                return t
        await asyncio.sleep(0.05)
    return None


async def _drain_sink(queue, frags) -> None:
    """Consume an in-process subscriber queue, logging (t, keyframe)
    per media fragment — the production fan-out path, minus a socket."""
    try:
        while True:
            item = await queue.get()
            if item[0] == "frag":
                frags.append((time.perf_counter(),
                              bool(len(item) > 2 and item[2])))
    except asyncio.CancelledError:
        pass


# -- component harness: TURN refresh failure -> re-allocation ------------

class _ScriptedTurnWire:
    """In-process TURN responder: answers Allocate/Refresh/
    CreatePermission success so the allocation client's recovery path
    (refresh 401 via the fault point -> bounded re-allocate) runs
    without a TURN server on the wire."""

    def __init__(self, alloc):
        from ..webrtc import stun

        self.stun = stun
        self.alloc = alloc
        self.allocates = 0

    # asyncio.DatagramTransport surface the client uses
    def sendto(self, wire, addr=None):
        stun = self.stun
        try:
            req = stun.StunMessage.decode(wire)
        except ValueError:
            return
        if req.mtype == stun.ALLOCATE_REQUEST:
            self.allocates += 1
            resp = stun.StunMessage(stun.ALLOCATE_SUCCESS, txid=req.txid)
            resp.add_xor_address(stun.ATTR_XOR_RELAYED_ADDRESS,
                                 "203.0.113.7", 40000 + self.allocates)
            resp.add_xor_address(stun.ATTR_XOR_MAPPED_ADDRESS,
                                 "198.51.100.1", 50000)
            resp.attrs[stun.ATTR_LIFETIME] = struct.pack(">I", 600)
        elif req.mtype == stun.REFRESH_REQUEST:
            resp = stun.StunMessage(stun.REFRESH_SUCCESS, txid=req.txid)
            resp.attrs[stun.ATTR_LIFETIME] = struct.pack(">I", 600)
        elif req.mtype == stun.CREATE_PERMISSION_REQUEST:
            resp = stun.StunMessage(stun.CREATE_PERMISSION_SUCCESS,
                                    txid=req.txid)
        else:
            return
        self.alloc.datagram_received(resp.encode(), ("turn.test", 3478))

    def close(self):
        pass


async def _turn_refresh_scenario() -> dict:
    """turn_refresh_401: refresh rejected -> log-once -> bounded
    re-allocate restores the relay."""
    from ..webrtc.turn_client import TurnAllocation

    alloc = TurnAllocation(("turn.test", 3478), "user", "pass")
    wire = _ScriptedTurnWire(alloc)
    alloc._transport = wire           # skip the real UDP bind
    try:
        await alloc._do_allocate()
        first_relay = alloc.relayed_addr
        await alloc.create_permission("198.51.100.2")
        rfaults.arm("turn_refresh_401", count=1)
        t0 = time.perf_counter()
        ok = await alloc._refresh_once()
        recovery_ms = (time.perf_counter() - t0) * 1e3
        recovered = (ok and alloc.relayed_addr is not None
                     and alloc.relayed_addr != first_relay
                     and wire.allocates >= 2
                     and "198.51.100.2" in alloc._permissions)
        return {"fired": 1, "recovered": bool(recovered),
                "recovery_ms": round(recovery_ms, 1)}
    finally:
        alloc._transport = None       # the scripted wire has no socket
        alloc._closed = True


# -- component harness: SCTP data-channel input under packet loss --------

def _sctp_loop_pair(wire, rto_initial: float = 0.1,
                    rto_min: float = 0.05):
    """A client/server association pair wired through one deque — the
    packet-level loopback every SCTP scenario runs on (the association
    is transport-agnostic; DTLS is exercised by the CI stock-client
    smoke, which needs libssl)."""
    from ..webrtc.sctp import SctpAssociation

    server = SctpAssociation(role="server",
                             on_transmit=lambda p: wire.append(("c", p)),
                             rto_initial=rto_initial, rto_min=rto_min)
    client = SctpAssociation(role="client",
                             on_transmit=lambda p: wire.append(("s", p)),
                             rto_initial=rto_initial, rto_min=rto_min)

    def pump():
        while wire:
            dst, pkt = wire.popleft()
            (client if dst == "c" else server).receive(pkt)

    return client, server, pump


async def _sctp_input_scenario(recovery_budget_s: float) -> dict:
    """sctp_drop_burst: a scripted stock-selkies double types over the
    ``input`` data channel while the fault swallows outbound packets
    mid-burst.  Every keystroke must land at the X input backend, in
    order, redelivered by retransmission (the harness only polls the
    timers) — the ISSUE 11 acceptance run."""
    import types
    from collections import deque

    from ..webrtc.datachannel import DataChannelEndpoint
    from .input import FakeBackend, Injector
    from .selkies_shim import attach_input_channels

    loop = asyncio.get_running_loop()
    wire: deque = deque()
    client, server, pump = _sctp_loop_pair(wire)
    backend = FakeBackend()
    injector = Injector(backend)
    session = types.SimpleNamespace(stats_summary=lambda: {})
    peer = types.SimpleNamespace(on_datachannel=None, close_hooks=[])
    attach_input_channels(peer, session, injector, loop=loop)
    DataChannelEndpoint(server, dtls_role="server",
                        on_channel=peer.on_datachannel)
    client_dc = DataChannelEndpoint(client, dtls_role="client")
    client.connect()
    pump()
    ch = client_dc.open("input")
    pump()

    fired_before = rfaults.points()["sctp_drop_burst"].fired
    expect = []
    t0 = time.perf_counter()
    keysyms = list(range(97, 117))           # 20 keys = 40 events
    for i, ks in enumerate(keysyms):
        if i == len(keysyms) // 2:           # mid-typing, as specified
            rfaults.arm("sctp_drop_burst", count=4)
        ch.send(f"k,{ks},1")
        ch.send(f"k,{ks},0")
        expect += [("key", ks, True), ("key", ks, False)]
        pump()
        await asyncio.sleep(0)               # let the input worker run
    deadline = time.perf_counter() + recovery_budget_s
    while (len(backend.events) < len(expect)
           and time.perf_counter() < deadline):
        client.poll_timeout()
        server.poll_timeout()
        pump()
        await asyncio.sleep(0.02)
    await asyncio.sleep(0.05)                # drain the worker's tail
    fired = rfaults.points()["sctp_drop_burst"].fired - fired_before
    rfaults.disarm("sctp_drop_burst")
    retransmits = client.retransmits + server.retransmits
    ordered_ok = backend.events == expect
    for hook in peer.close_hooks:
        hook()
    client.close()
    server.close()
    return {
        "fired": fired,
        # the acceptance bar: every event delivered IN ORDER, the burst
        # really fired, and recovery came from retransmission
        # (dngd_sctp_retransmits_total > 0)
        "recovered": bool(ordered_ok and fired > 0 and retransmits > 0),
        "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "retransmits": retransmits,
        "events_delivered": len(backend.events),
        "events_expected": len(expect),
    }


async def _dcep_stall_scenario(recovery_budget_s: float) -> dict:
    """dcep_open_stall: the DATA_CHANNEL_ACK for an inbound OPEN is
    delayed; the deferred flush must complete the open and the channel
    must then carry data."""
    from collections import deque

    from ..webrtc.datachannel import DataChannelEndpoint

    wire: deque = deque()
    client, server, pump = _sctp_loop_pair(wire)
    server_dc = DataChannelEndpoint(server, dtls_role="server")
    client_dc = DataChannelEndpoint(client, dtls_role="client")
    client.connect()
    pump()
    rfaults.arm("dcep_open_stall", count=1, delay_ms=150)
    t0 = time.perf_counter()
    ch = client_dc.open("input")
    pump()
    stalled = ch.state == "opening"          # the ACK really deferred
    fired = 1 - rfaults.armed_count("dcep_open_stall")
    deadline = time.perf_counter() + recovery_budget_s
    while ch.state != "open" and time.perf_counter() < deadline:
        server_dc.poll()
        client.poll_timeout()
        server.poll_timeout()
        pump()
        await asyncio.sleep(0.02)
    rfaults.disarm("dcep_open_stall")
    got = []
    srv_ch = server_dc.channels.get(ch.stream_id)
    if srv_ch is not None:
        srv_ch.on_message = got.append
    ch.send("k,97,1")
    pump()
    recovered = bool(stalled and ch.state == "open" and got == ["k,97,1"])
    client.close()
    server.close()
    return {"fired": fired, "recovered": recovered,
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1)}


# -- RTCP feedback plane: loss repair, congestion, PLI storms ------------

async def _rtp_loss_scenario(recovery_budget_s: float) -> dict:
    """rtp_loss_burst: a 4-packet burst is tail-dropped mid-stream by
    the seeded impairment shim; the receiver NACKs the holes, the
    send-history ring answers with RTX retransmissions, and every frame
    arrives contiguous at the sink — with NO keyframe spent (repair
    happens *below* the quality ladder)."""
    from ..webrtc import rtcp as wrtcp
    from ..webrtc.feedback import FeedbackPlane, FeedbackSink, Pacer
    from ..webrtc.rtp import RtpStream
    from .impair import ImpairedLink

    sink_box: list = []
    link = ImpairedLink(lambda p: sink_box[0].on_rtp(p), seed=14,
                        jitter_ms=2.0, reorder=0.05)
    stream = RtpStream(96)
    pacer = Pacer(link.send)
    plane = FeedbackPlane(stream, link.send, pacer=pacer)
    plane.nack_enabled = True
    plane.enable_rtx(97)
    idr_requests: list = []
    plane.on_keyframe_request = idr_requests.append

    def on_rtcp(pkt: bytes) -> None:
        # receiver -> sender feedback path (lossless uplink, like RTCP
        # over the healthy reverse direction)
        for p in wrtcp.parse_compound(pkt):
            if p.get("nack_seqs"):
                plane.on_nack(p["nack_seqs"])

    sink = FeedbackSink(on_rtcp, stream.ssrc, rtx_ssrc=plane.rtx.ssrc)
    sink_box.append(sink)

    n_frames = 40
    fired_before = rfaults.points()["rtp_loss_burst"].fired
    t0 = time.perf_counter()
    for f in range(n_frames):
        if f == n_frames // 2:      # mid-stream, as specified
            rfaults.arm("rtp_loss_burst", count=1, packets=4)
        plane.send_frame([b"\x65" + b"\x00" * 1099] * 8, f * 3000)
        link.pump()
        sink.poll()
        await asyncio.sleep(0.01)
        link.pump()
        sink.poll()
    # drain: retransmissions + jittered stragglers
    deadline = time.perf_counter() + recovery_budget_s
    while ((sink.missing() or link.pending()
            or sink.frames + sink.frame_gaps < n_frames)
           and time.perf_counter() < deadline):
        link.pump()
        sink.poll()
        await asyncio.sleep(0.01)
    fired = rfaults.points()["rtp_loss_burst"].fired - fired_before
    rfaults.disarm("rtp_loss_burst")
    pacer.close()
    link.close()
    recovered = bool(
        fired == 1
        and plane.retransmits >= 1          # NACK-driven repair
        and sink.frames == n_frames         # contiguous at the sink
        and sink.frame_gaps == 0            # zero frame gaps
        and len(idr_requests) == 0)         # and NO IDR spent
    return {
        "fired": fired, "recovered": recovered,
        "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "retransmits": plane.retransmits,
        "frames_delivered": sink.frames,
        "frame_gaps": sink.frame_gaps,
        "idr_requests": len(idr_requests),
        "nacks": sink.nacks_sent,
        "link": link.stats(),
    }


async def _remb_cap_scenario(cfg, session,
                             recovery_budget_s: float) -> dict:
    """Sustained bandwidth cap: the receiver's REMB converges on the
    cap, the headroom gauge drops below the congestion threshold, and
    the ladder walks DOWN on the forward signal alone (the latency
    budget is parked out of reach); lifting the cap restores."""
    from ..webrtc.feedback import FeedbackPlane, FeedbackSink, Pacer
    from ..webrtc.rtp import RtpStream
    from .impair import ImpairedLink

    sink_box: list = []
    # ~300 kbps bottleneck vs ~1.7 Mbps offered media
    link = ImpairedLink(lambda p: sink_box[0].on_rtp(p), seed=15,
                        bandwidth_bps=300_000.0)
    stream = RtpStream(96)
    pacer = Pacer(link.send)
    plane = FeedbackPlane(stream, link.send, pacer=pacer)

    def on_rtcp(pkt: bytes) -> None:
        from ..webrtc import rtcp as wrtcp

        for p in wrtcp.parse_compound(pkt):
            if "remb" in p:
                plane.on_remb(p["remb"]["bitrate_bps"],
                              p["remb"]["ssrcs"])

    # NACK disabled (interval parked): this scenario isolates the
    # congestion signal; the loss-repair loop is scenario rtp_loss_burst
    sink = FeedbackSink(on_rtcp, stream.ssrc,
                        nack_interval_s=1e9, give_up_s=0.2)
    sink_box.append(sink)

    ctl = DegradeController(
        SessionExecutor(session, cfg=cfg),
        budget_ms=1e9,                 # only REMB may move the ladder
        window=60, min_frames=8, breach_ticks=2, recover_ticks=3,
        cooldown_s=0.1, max_level=2)
    out: dict = {"ladder": [s.name for s in ctl.steps]}

    async def media_until(pred, budget_s: float) -> bool:
        deadline = time.perf_counter() + budget_s
        f = 0
        while time.perf_counter() < deadline:
            plane.send_frame([b"\x41" * 1100] * 6, f * 3000)
            f += 1
            link.pump()
            sink.poll(remb=True)
            ctl.tick()
            if pred():
                return True
            await asyncio.sleep(1 / 30)
            link.pump()
        return False

    t0 = time.perf_counter()
    try:
        engaged = await media_until(lambda: ctl.level >= 2,
                                    recovery_budget_s * 2)
        out["engaged"] = engaged
        out["capped_headroom"] = ctl.snapshot()["remb_headroom"]
        link.set_bandwidth(None)       # bottleneck lifted
        restored = await media_until(lambda: ctl.level == 0,
                                     recovery_budget_s * 2)
        out["restored_headroom"] = ctl.snapshot()["remb_headroom"]
        out["recovered"] = bool(engaged and restored)
        out["transitions"] = ctl.transitions
        out["recovery_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    finally:
        ctl.stop()
        plane.close()                  # retire the REMB series so the
        pacer.close()                  # later scenarios read None
        link.close()
        session.set_qp_offset(0)
        session.set_fps_cap(None)
    return out


async def _pli_storm_scenario(session,
                              recovery_budget_s: float) -> dict:
    """pli_storm: one RTCP arrival dispatches a burst of PLIs; the
    session's rate-limited ``request_idr`` must grant EXACTLY ONE
    keyframe inside the rate window (the rest collapse into a single
    deferred grant after it)."""
    from ..webrtc import rtcp as wrtcp

    monitor = wrtcp.PeerRtcpMonitor({0xFEED: ("video", 90_000)})
    granted: list = []

    def on_pli(kind: str, source: str) -> None:
        if session.request_idr(source):
            granted.append(source)

    monitor.on_pli = on_pli
    # let the rate window reopen ORGANICALLY (any earlier scenario's
    # grant + a possible deferred grant both age out) — no reaching
    # into the session's limiter internals, so the scenario works
    # against any session type carrying the request_idr contract
    await asyncio.sleep(2 * session.IDR_MIN_INTERVAL_S + 0.3)
    plis = 10
    rfaults.arm("pli_storm", count=1, plis=plis)
    t0 = time.perf_counter()
    # the storm rides an otherwise-ordinary RTCP arrival
    monitor.ingest(wrtcp.receiver_report(0x1, []))
    fired = 1 - rfaults.armed_count("pli_storm")
    rfaults.disarm("pli_storm")
    # the 9 over-limit requests must have collapsed into one pending
    # deferred grant (observable via the public contract: a fresh
    # request inside the window is NOT granted)
    deferred_window = session.request_idr("pli") is False
    monitor.close()
    recovered = bool(fired == 1 and len(granted) == 1
                     and deferred_window)
    return {
        "fired": fired, "recovered": recovered,
        "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "plis": plis,
        "idr_granted_in_window": len(granted),
        "window_still_closed": deferred_window,
    }


# -- quality plane: forced PSNR-floor breach -> event + flight dump ------

async def _content_breach_scenario(session, port,
                                   recovery_budget_s: float) -> dict:
    """Park the quality plane's PSNR floor above any achievable
    fidelity (DNGD_CONTENT_PSNR_FLOOR=99); the in-graph PSNR of the
    very next sampled frame sits below it, so a ``psnr_floor_breach``
    event must land on the fleet timeline (visible at /debug/events)
    and the flight recorder's triggered dump must embed the content
    state block — the ISSUE 17 observability acceptance run.  The floor
    is restored afterwards, so later scenarios see the real config."""
    import os

    import aiohttp

    from ..obs import events as obse
    from ..obs import flight as obsf

    def breach_count() -> int:
        return sum(1 for e in obse.EVENTS.recent(1024)
                   if e.get("kind") == "psnr_floor_breach")

    before = breach_count()
    old = os.environ.get("DNGD_CONTENT_PSNR_FLOOR")
    os.environ["DNGD_CONTENT_PSNR_FLOOR"] = "99"
    t0 = time.perf_counter()
    try:
        deadline = time.perf_counter() + recovery_budget_s
        while (breach_count() == before
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.05)
    finally:
        if old is None:
            os.environ.pop("DNGD_CONTENT_PSNR_FLOOR", None)
        else:
            os.environ["DNGD_CONTENT_PSNR_FLOOR"] = old
    emitted = breach_count() - before
    # the event must be CLIENT-visible, not just in-process
    async with aiohttp.ClientSession() as http:
        async with http.get(
                f"http://127.0.0.1:{port}/debug/events") as resp:
            events_text = await resp.text()
    visible = "psnr_floor_breach" in events_text
    dump = obsf.FLIGHT.find_dump("psnr_floor_breach")
    content = (dump or {}).get("content") or {}
    dump_ok = bool(dump and content.get("sessions"))
    return {
        "fired": emitted,
        "recovered": bool(emitted >= 1 and visible and dump_ok),
        "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "event_visible": visible,
        "flight_dump": bool(dump),
        "flight_content_block": dump_ok,
    }


# -- damage plane: calm -> full-frame spike -> event, charge, no shed ----

async def _damage_spike_scenario(session, port, frags,
                                 recovery_budget_s: float) -> dict:
    """A calm desktop jumping to a full-frame change (ISSUE 20): the
    departure must surface as a ``damage_spike`` timeline event
    (client-visible at /debug/events) with a flight dump carrying the
    content block, the capacity charge must ride to full cost
    (placement priced the spike headroom in advance), and the serving
    co-tenant must keep streaming — a spike engages the backpressure
    ladder, never the shed list.  The spike is driven through the
    content plane's real record path under a scenario session id (the
    loopback's own content mix is not steerable from here), so
    emission, the calm-history rule, debounce, charge, and the dump
    trigger all exercise production code."""
    import aiohttp

    from ..obs import content as obsc
    from ..obs import events as obse
    from ..obs import flight as obsf

    sid = "chaos-damage-spike"
    plane = obsc.PLANE

    def spike_count() -> int:
        return sum(1 for e in obse.EVENTS.recent(1024)
                   if e.get("kind") == "damage_spike")

    before = spike_count()
    t0 = time.perf_counter()
    calm_charge = spike_charge = None
    try:
        # 31 calm frames: the spike rule requires calm history to
        # depart from (median of the prior window <= thr/2)
        for _ in range(31):
            plane.record(sid, {"damage_fraction": 0.02})
        calm_charge = plane.damage_charge(sid)
        plane.record(sid, {"damage_fraction": 1.0})      # the spike
        spike_charge = plane.damage_charge(sid)
        deadline = time.perf_counter() + recovery_budget_s
        while (spike_count() == before
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.05)
        emitted = spike_count() - before
        async with aiohttp.ClientSession() as http:
            async with http.get(
                    f"http://127.0.0.1:{port}/debug/events") as resp:
                events_text = await resp.text()
        visible = "damage_spike" in events_text
        dump = obsf.FLIGHT.find_dump("damage_spike")
        dump_ok = bool(dump
                       and (dump.get("content") or {}).get("sessions"))
        # the REAL serving session must still be delivering media
        flow = await _await_frag(frags, t0, recovery_budget_s)
    finally:
        plane.drop(sid)
    charged = (calm_charge is not None and calm_charge < 0.5
               and spike_charge is not None and spike_charge >= 0.99)
    return {
        "fired": emitted,
        "recovered": bool(emitted >= 1 and visible and dump_ok
                          and charged and flow is not None),
        "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "event_visible": visible,
        "flight_dump": bool(dump),
        "flight_content_block": dump_ok,
        "calm_charge": calm_charge,
        "spike_charge": spike_charge,
        "cotenant_flow": flow is not None,
    }


# -- continuity: device preemption with SSRC/seq lineage assertions ------

class _RtpTap:
    """Peer-equivalent RTP packetizer riding the AU-listener path.

    A live WebRTC peer holds one :class:`..webrtc.rtp.RtpStream` whose
    SSRC and sequence counter persist for the peer's lifetime; device
    recovery swaps the ENCODER but never the peer, so continuity on the
    wire follows from this object surviving.  The tap IS that object —
    it packetizes every delivered AU exactly like the peer's video
    track and records what hit the (virtual) wire, so the bench asserts
    the client-visible contract: one SSRC, contiguous sequence numbers,
    a bounded AU gap across recovery, and a keyframe first after it."""

    def __init__(self, codec_name: str):
        from ..webrtc.rtp import RtpStream

        self.codec = codec_name
        self.stream = RtpStream(96)
        self.ssrcs = set()
        self.seqs: list = []
        self.aus: list = []            # (t, keyframe)

    def on_au(self, au: bytes, keyframe: bool, pts: int) -> None:
        from ..webrtc.rtp import packetize_h264, packetize_vp8, parse_header
        from .mp4 import split_annexb

        if self.codec.startswith("h264"):
            payloads = packetize_h264(split_annexb(au))
        elif self.codec.startswith("vp8"):
            payloads = packetize_vp8(au)
        else:
            payloads = [au]
        for pkt in self.stream.packetize(payloads, pts & 0xFFFFFFFF):
            hdr = parse_header(pkt)
            self.ssrcs.add(hdr["ssrc"])
            self.seqs.append(hdr["seq"])
        self.aus.append((time.perf_counter(), bool(keyframe)))

    def seq_contiguous(self) -> bool:
        return all((b - a) & 0xFFFF == 1
                   for a, b in zip(self.seqs, self.seqs[1:]))

    async def await_au(self, after_t: float, deadline_s: float,
                       require_key: bool = False) -> Optional[float]:
        deadline = time.perf_counter() + deadline_s
        while time.perf_counter() < deadline:
            for t, key in reversed(self.aus):
                if t > after_t and (key or not require_key):
                    return t
            await asyncio.sleep(0.05)
        return None


async def _device_preempt_scenario(session, recovery_budget_s: float
                                   ) -> dict:
    """Preempt the device mid-GOP; the session must re-acquire, restore
    the encoder-state checkpoint and resume THE SAME stream lineage."""
    tap = _RtpTap(session.codec_name)
    session.add_au_listener(tap.on_au)
    try:
        if await tap.await_au(0.0, recovery_budget_s) is None:
            return {"fired": 0, "recovered": False,
                    "error": "no AU before injection"}
        pre_recoveries = session._recoveries
        muxer_before = session.muxer          # hold the OBJECT: an id()
        # compare could false-pass on address reuse after a rebuild
        last_before = tap.aus[-1][0]
        rfaults.arm("device_preempt", count=1)
        t0 = time.perf_counter()
        while (rfaults.armed_count("device_preempt")
               and time.perf_counter() - t0 < recovery_budget_s):
            await asyncio.sleep(0.05)
        t_fired = time.perf_counter()         # pre-arm pipelined AUs
        fired = 1 - rfaults.armed_count("device_preempt")
        rfaults.disarm("device_preempt")
        # the recovery must COMPLETE (counter increments) before any
        # keyframe can be the recovery IDR — a scheduled GOP keyframe
        # landing between arm and fire must not satisfy the wait
        deadline = time.perf_counter() + recovery_budget_s
        while (session._recoveries == pre_recoveries
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.05)
        t_rec = (await tap.await_au(t_fired, recovery_budget_s,
                                    require_key=True)
                 if session._recoveries > pre_recoveries else None)
        alive = session._thread is not None and session._thread.is_alive()
        gap_ms = (None if t_rec is None
                  else round((t_rec - last_before) * 1e3, 1))
        gap_bounded = (gap_ms is not None
                       and gap_ms <= recovery_budget_s * 1e3)
        ckpt_restored = session._ckpt.state is not None
        # the verdict carries EVERY acceptance clause (bounded frame
        # gap, checkpoint actually restored) so a standalone bench run
        # exits non-zero on a regression — not just the CI assertions
        recovered = bool(
            fired == 1 and t_rec is not None and alive
            and session._recoveries == pre_recoveries + 1
            and len(tap.ssrcs) == 1           # same SSRC across recovery
            and tap.seq_contiguous()          # no RTP sequence break
            and session.muxer is muxer_before  # timestamp lineage
            and gap_bounded and ckpt_restored)
        return {
            "fired": fired, "recovered": recovered,
            "recovery_ms": (None if t_rec is None
                            else round((t_rec - t0) * 1e3, 1)),
            "frame_gap_ms": gap_ms,
            "frame_gap_bounded": gap_bounded,
            "ssrc_count": len(tap.ssrcs),
            "seq_contiguous": tap.seq_contiguous(),
            "recoveries": session._recoveries,
            "checkpoint_restored": ckpt_restored,
        }
    finally:
        rfaults.disarm("device_preempt")
        session.remove_au_listener(tap.on_au)


# -- continuity: mesh chip loss -> N->N-1 re-bucket ----------------------

async def _mesh_failover_scenario(quick: bool,
                                  recovery_budget_s: float,
                                  timeout_s: float) -> dict:
    """Drop one chip of a live multi-session mesh mid-GOP; surviving
    chips re-bucket and every session resumes from its recovery IDR.
    Needs >= 2 devices (CI forces host-platform devices; a single
    chip reports skipped)."""
    import jax

    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"{ndev} device(s); elastic failover needs "
                           ">= 2", "recovered": None}
    from .multisession import BatchStreamManager

    n_sessions = min(ndev, 8)
    # full mode runs the acceptance geometry (8x1080p -> 7 chips);
    # quick keeps CI on a compile-friendly bucket
    w, h = (128, 96) if quick else (1920, 1080)
    cfg = serving_budget_config(w, h, 30, extra={
        "TPU_SESSIONS": str(n_sessions),
        "TPU_MESH": str(n_sessions),
        "ENCODER_GOP": "30",
        "WEBRTC_ENABLE_RESIZE": "true",
    })
    loop = asyncio.get_running_loop()
    from ..rfb.source import SyntheticSource
    sources = [SyntheticSource(w, h, fps=float(cfg.refresh))
               for _ in range(n_sessions)]
    mgr = BatchStreamManager(cfg, sources, loop=loop)
    mgr.start()
    sinks = [mgr.session(i).subscribe() for i in range(n_sessions)]
    frag_logs: list = [[] for _ in range(n_sessions)]
    drains = [asyncio.ensure_future(_drain_sink(q, f))
              for q, f in zip(sinks, frag_logs)]
    try:
        # warm up: a keyframe on every hub proves the compiled IDR step
        for frags in frag_logs:
            if await _await_frag(frags, 0.0, timeout_s,
                                 require_key=True) is None:
                return {"fired": 0, "recovered": False,
                        "error": "no first frame before chip loss"}
        # ... and a SECOND keyframe on hub 0 proves a full GOP of P
        # ticks ran, i.e. the P-step compile is behind us — otherwise
        # that compile stalls the loop across the fault-consumption
        # window below and the injection looks like it never fired
        if await _await_frag(frag_logs[0], time.perf_counter(),
                             timeout_s, require_key=True) is None:
            return {"fired": 0, "recovered": False,
                    "error": "no second GOP before chip loss"}
        mesh_before = list(mgr.mesh.devices.shape)
        rfaults.arm("mesh_chip_lost", count=1)
        t0 = time.perf_counter()
        while (rfaults.armed_count("mesh_chip_lost")
               and time.perf_counter() - t0 < timeout_s):
            await asyncio.sleep(0.05)
        fired = 1 - rfaults.armed_count("mesh_chip_lost")
        rfaults.disarm("mesh_chip_lost")
        # every surviving session must deliver its recovery IDR (the
        # rebuilt step recompiles, so the wait rides the full timeout)
        t_rebuilt = time.perf_counter()
        recovered_all = True
        for frags in frag_logs:
            if await _await_frag(frags, t_rebuilt, timeout_s,
                                 require_key=True) is None:
                recovered_all = False
                break
        alive = mgr._thread is not None and mgr._thread.is_alive()
        stats = mgr.stats_summary()
        return {
            "fired": fired,
            "recovered": bool(fired == 1 and recovered_all and alive
                              and mgr._rebuilds >= 1),
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
            "sessions": n_sessions,
            "mesh_before": mesh_before,
            "mesh_after": list(mgr.mesh.devices.shape),
            "dead_chips": stats["dead_chips"],
            "geometry": stats["geometry"],
        }
    finally:
        rfaults.disarm("mesh_chip_lost")
        for d in drains:
            d.cancel()
        mgr.close()


# -- continuity: rolling restart -> drain-to-migrate handoff -------------

async def _rolling_restart_scenario(recovery_budget_s: float,
                                    timeout_s: float) -> dict:
    """Restart the serving process under live clients (ISSUE 19): the
    predecessor's drain MIGRATES — encoder lineage + wire continuity
    spool through DNGD_HANDOFF_DIR, the successor adopts them before
    its first frame, and the client resumes with its token seeing the
    SAME SSRC, contiguous RTP sequence numbers, exactly one recovery
    IDR and ZERO sheds.  A rolling restart must be a non-event on the
    wire.  The entry carries no ``fired`` key: a restart is not an
    rfaults injection point, so the per-fault flight accounting below
    skips it (like ``content_quality``)."""
    import shutil
    import tempfile

    import aiohttp

    from ..rfb.source import SyntheticSource
    from .server import bound_port, serve
    from .session import StreamSession

    tmpdir = tempfile.mkdtemp(prefix="dngd-handoff-")
    w, h = 128, 96
    cfg = serving_budget_config(w, h, 30, extra={
        "FLEET_ENABLE": "true",
        "DNGD_HANDOFF_DIR": tmpdir,
        # generous TTL: the successor's first compile must never race
        # the resume token out of its pending window on a loaded box
        "DNGD_HANDOFF_TOKEN_TTL_S": "600",
        # a LONG GOP isolates the recovery IDR: any keyframe the
        # successor emits inside the observation window is the resume
        # IDR, never a scheduled GOP boundary
        "ENCODER_GOP": "120",
        "DEGRADE_ENABLE": "false",
    })
    loop = asyncio.get_running_loop()
    out: dict = {"recovered": False}
    t0 = time.perf_counter()
    session_a = session_b = None
    runner_a = runner_b = None
    tap_a = tap_b = None
    try:
        # ---- generation A: live stream + one resumable client --------
        source_a = SyntheticSource(w, h, fps=float(cfg.refresh))
        session_a = StreamSession(cfg, source_a, loop=loop)
        tap_a = _RtpTap(session_a.codec_name)
        session_a.add_au_listener(tap_a.on_au)
        session_a.start()
        runner_a = await serve(cfg, session_a)
        port_a = bound_port(runner_a)
        hmgr_a = runner_a.app["handoff"]
        fleet_a = runner_a.app["fleet"]
        migrate_msg = None
        async with aiohttp.ClientSession() as http:
            async with http.ws_connect(f"http://127.0.0.1:{port_a}/ws",
                                       max_msg_size=0) as ws:
                hello = await ws.receive_json(timeout=timeout_s)
                token = hello.get("resume")
                out["token_issued"] = bool(token)
                if not token:
                    out["error"] = "no resume token in hello"
                    return out
                # the tap IS this client's wire state: the same video
                # RtpStream a live peer's export_wire would snapshot
                hmgr_a.attach_wire(
                    token,
                    lambda: {"video": tap_a.stream.export_state()})
                if await tap_a.await_au(0.0, timeout_s,
                                        require_key=True) is None:
                    out["error"] = "no keyframe before restart"
                    return out
                # drain-to-migrate: the preStop-hook path (SIGTERM
                # drives the same handoff_migrate coroutine)
                async with http.post(
                        f"http://127.0.0.1:{port_a}/debug/drain") as r:
                    body = await r.json()
                out["handoff"] = body.get("handoff")
                # the connected client must be handed its resume token
                deadline = time.perf_counter() + recovery_budget_s
                while time.perf_counter() < deadline:
                    msg = await ws.receive(timeout=max(
                        0.1, deadline - time.perf_counter()))
                    if msg.type == aiohttp.WSMsgType.TEXT:
                        data = json.loads(msg.data)
                        if data.get("type") == "migrate":
                            migrate_msg = data
                            break
                    elif msg.type in (aiohttp.WSMsgType.CLOSED,
                                      aiohttp.WSMsgType.CLOSE,
                                      aiohttp.WSMsgType.ERROR):
                        break
        out["migrate_notified"] = migrate_msg is not None
        if migrate_msg is None:
            out["error"] = "no migrate message before socket close"
            return out
        token = migrate_msg.get("resume") or token
        seq_a_last = tap_a.seqs[-1] if tap_a.seqs else None
        sheds_a = fleet_a.sheds if fleet_a is not None else 0
        # the predecessor process generation ends here
        session_a.remove_au_listener(tap_a.on_au)
        session_a.close()
        await runner_a.cleanup()
        runner_a = None

        # ---- generation B: adopt the spool, resume the client --------
        source_b = SyntheticSource(w, h, fps=float(cfg.refresh))
        session_b = StreamSession(cfg, source_b, loop=loop)
        # serve() consumes the spool BEFORE the session starts, so the
        # adoption is queued ahead of frame 0 and the successor's first
        # frame continues the predecessor's GOP (no fresh-start IDR)
        runner_b = await serve(cfg, session_b)
        port_b = bound_port(runner_b)
        hmgr_b = runner_b.app["handoff"]
        fleet_b = runner_b.app["fleet"]
        staged = dict(hmgr_b._pending.get(token) or {})
        wire = staged.get("wire") or {}
        out["wire_staged"] = bool(wire.get("video"))
        session_b.start()
        deadline = time.perf_counter() + timeout_s
        while (not session_b._handoff_adopted
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.05)
        out["adopted"] = session_b._handoff_adopted
        # the successor-side tap seeds from the staged wire exactly as
        # _handle_offer seeds a resuming peer (peer.import_wire): the
        # sequence frontier crossed the process boundary in the spool
        tap_b = _RtpTap(session_b.codec_name)
        if wire.get("video"):
            tap_b.stream.import_state(wire["video"])
        session_b.add_au_listener(tap_b.on_au)
        # flush the tap-attach forced keyframe BEFORE reconnecting so
        # the exactly-one-IDR count below sees only the resume IDR
        await tap_b.await_au(0.0, recovery_budget_s, require_key=True)
        t_reconnect = time.perf_counter()
        hello_b = None
        async with aiohttp.ClientSession() as http:
            async with http.ws_connect(
                    f"http://127.0.0.1:{port_b}/ws?resume={token}",
                    max_msg_size=0) as ws2:
                hello_b = await ws2.receive_json(timeout=timeout_s)
                # the join-subscribe keyframe and request_idr("handoff")
                # must collapse into ONE recovery IDR on the wire
                t_idr = await tap_b.await_au(t_reconnect,
                                             recovery_budget_s,
                                             require_key=True)
                if t_idr is not None:
                    # settle: a second IDR inside the long GOP would be
                    # a resume-storm leak, not a scheduled keyframe
                    await asyncio.sleep(1.0)
        out["resumed"] = bool(hello_b and hello_b.get("resumed"))
        keys_after_resume = sum(1 for t, k in tap_b.aus
                                if k and t > t_reconnect)
        async with aiohttp.ClientSession() as http:
            async with http.get(
                    f"http://127.0.0.1:{port_b}/metrics") as resp:
                metrics_b = await resp.text()
        seq_boundary_ok = (
            seq_a_last is not None and bool(tap_b.seqs)
            and (tap_b.seqs[0] - seq_a_last) & 0xFFFF == 1)
        alive = (session_b._thread is not None
                 and session_b._thread.is_alive())
        sheds_b = fleet_b.sheds if fleet_b is not None else 0
        migs_b = fleet_b.migrations if fleet_b is not None else 0
        out.update({
            "migrated": int((out.get("handoff") or {})
                            .get("migrated") or 0),
            "ssrc_count": len(tap_a.ssrcs | tap_b.ssrcs),
            "seq_contiguous": (tap_a.seq_contiguous()
                               and tap_b.seq_contiguous()),
            "seq_boundary_contiguous": seq_boundary_ok,
            "recovery_idr": t_idr is not None,
            "idrs_after_resume": keys_after_resume,
            "sheds": sheds_a + sheds_b,
            "migrations_admitted": migs_b,
            "metrics_visible": (
                "dngd_handoff_sessions_total" in metrics_b
                and "dngd_handoff_resume_total" in metrics_b),
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1),
        })
        out["recovered"] = bool(
            out["migrated"] >= 1 and out["adopted"]
            and out["wire_staged"] and out["resumed"]
            and t_idr is not None and keys_after_resume == 1
            and len(tap_a.ssrcs | tap_b.ssrcs) == 1  # same SSRC across
            and out["seq_contiguous"] and seq_boundary_ok
            and sheds_a == 0 and sheds_b == 0         # zero sheds
            and migs_b >= 1
            and out["metrics_visible"] and alive)
        return out
    finally:
        for sess, tap in ((session_a, tap_a), (session_b, tap_b)):
            if sess is not None and tap is not None:
                sess.remove_au_listener(tap.on_au)
        for sess in (session_a, session_b):
            if sess is not None:
                sess.close()
        for runner in (runner_a, runner_b):
            if runner is not None:
                await runner.cleanup()
        shutil.rmtree(tmpdir, ignore_errors=True)


# -- the chaos run -------------------------------------------------------

async def run_chaos(cfg: Optional[Config] = None,
                    width: int = 320, height: int = 240, fps: int = 30,
                    quick: bool = False,
                    recovery_budget_s: float = 30.0,
                    timeout_s: float = 600.0,
                    continuity: bool = True,
                    continuity_only: bool = False) -> dict:
    """Inject every canonical fault point; report per-fault recovery.

    ``continuity_only`` restricts the run to the session-continuity
    scenarios (``device_preempt`` + ``mesh_chip_lost``) — the CI
    continuity-smoke step; ``continuity=False`` skips them (the
    pre-existing chaos-smoke scope)."""
    from ..obs.budget import LEDGER
    from ..rfb.source import SyntheticSource
    from .server import bound_port, serve
    from .session import StreamSession

    if quick:
        width, height, fps = 128, 96, 30
    if cfg is None:
        cfg = serving_budget_config(width, height, fps, extra={
            "WEBRTC_ENABLE_RESIZE": "true",
            # a short checkpoint cadence so the preemption scenario
            # restores a real checkpoint, not the no-lineage fallback
            "DNGD_CKPT_INTERVAL": "1.0",
            # the scenarios drive their OWN fast-tick controller; the
            # server's 1 s-cadence one would fight it over the ladder
            "DEGRADE_ENABLE": "false"})
    rfaults.disarm_all()
    LEDGER.clear()
    # flight recorder: every injected fault must produce a postmortem
    # dump (counted per fault point, asserted in the report below)
    from ..obs import flight as obsf
    obsf.FLIGHT.clear()
    loop = asyncio.get_running_loop()
    source = SyntheticSource(cfg.sizew, cfg.sizeh, fps=float(cfg.refresh))
    session = StreamSession(cfg, source, loop=loop)
    session.start()
    runner = await serve(cfg, session)
    port = bound_port(runner)

    sink = session.subscribe()        # production fan-out, in-process sink
    frags: list = []
    drain = asyncio.ensure_future(_drain_sink(sink, frags))
    report: dict = {"mode": "chaos-loopback", "quick": quick,
                    "geometry": f"{cfg.sizew}x{cfg.sizeh}@{cfg.refresh}",
                    "faults": {}, "degrade": {}, "continuity": {}}
    t_start = time.perf_counter()

    async def serving_fault(name: str, count: int,
                            require_key: bool, **params) -> dict:
        t0 = time.perf_counter()
        rfaults.arm(name, count=count, **params)
        # wait until every armed firing was consumed (the fault actually
        # hit the path), then for the stream to resume past it
        while (rfaults.armed_count(name)
               and time.perf_counter() - t0 < recovery_budget_s):
            await asyncio.sleep(0.05)
        fired = count - rfaults.armed_count(name)
        rfaults.disarm(name)
        t_rec = await _await_frag(frags, time.perf_counter(),
                                  recovery_budget_s,
                                  require_key=require_key)
        alive = session._thread is not None and session._thread.is_alive()
        return {"fired": fired,
                "recovered": bool(t_rec is not None and alive
                                  and fired == count),
                "recovery_ms": (round((t_rec - t0) * 1e3, 1)
                                if t_rec is not None else None)}

    try:
        # warm up: the first keyframe proves compile + full path
        first = await _await_frag(frags, 0.0, timeout_s * 0.6,
                                  require_key=True)
        if first is None:
            raise RuntimeError("chaos: no first frame within budget")
        # Pre-compile the degraded-qp executables: the ladder's qp_up
        # step is one fresh jit specialization, and that compile must
        # land in WARMUP wall-clock, not inside a recovery budget (the
        # control loop under test is the ladder, not XLA).
        session.set_qp_offset(SessionExecutor.QP_STEP)
        session.request_keyframe()
        t = await _await_frag(frags, time.perf_counter(),
                              timeout_s * 0.3, require_key=True)
        if t is not None:                     # one P at the degraded qp
            await _await_frag(frags, t, 30.0)
        session.set_qp_offset(0)
        session.request_keyframe()
        await _await_frag(frags, time.perf_counter(), 30.0,
                          require_key=True)

        if not continuity_only:
            # 1) collect failure -> frame dropped, stale P suppressed,
            #    forced-IDR resync (recovery requires the IDR, not any
            #    frag)
            report["faults"]["collect_timeout"] = await serving_fault(
                "collect_timeout", count=2, require_key=True)

            # 2) submit failure -> frames dropped, breaker counts,
            #    session survives well under the open threshold
            report["faults"]["device_submit_error"] = await serving_fault(
                "device_submit_error", count=2, require_key=False)

            # 3) X server gone -> bounded retry until the source
            #    returns, then IDR resync
            report["faults"]["xserver_gone"] = await serving_fault(
                "xserver_gone", count=5, require_key=True)

            # 4) websocket send stall -> queue eviction then slow-
            #    subscriber eviction; the SESSION and the other
            #    (in-process) subscriber must be unaffected, and the
            #    evicted client can reconnect
            report["faults"]["ws_send_stall"] = await _ws_stall_scenario(
                cfg, session, port, frags, recovery_budget_s)

            # 5) TURN refresh failure -> bounded re-allocation
            #    (component harness on a scripted responder)
            report["faults"]["turn_refresh_401"] = \
                await _turn_refresh_scenario()

            # 5b) SCTP data-channel input: packet-loss burst mid-typing
            #     -> retransmission redelivers every keystroke in order
            #     (ISSUE 11 acceptance), and a stalled DCEP ACK still
            #     completes the channel open
            report["faults"]["sctp_drop_burst"] = \
                await _sctp_input_scenario(recovery_budget_s)
            report["faults"]["dcep_open_stall"] = \
                await _dcep_stall_scenario(recovery_budget_s)

            # 5c) RTCP feedback plane (ISSUE 14): a seeded loss burst
            #     repairs via NACK/RTX with contiguous frames and NO
            #     IDR; a PLI storm costs exactly one rate-limited IDR
            #     (the REMB bandwidth-cap scenario runs after 6, which
            #     rebuilds the whole degrade block)
            report["faults"]["rtp_loss_burst"] = \
                await _rtp_loss_scenario(recovery_budget_s)
            report["faults"]["pli_storm"] = \
                await _pli_storm_scenario(session, recovery_budget_s)

            # 5d) quality plane (ISSUE 17): a forced PSNR-floor breach
            #     must surface as a timeline event at /debug/events and
            #     a flight dump carrying the content-state block
            #     (separate report key: it is a telemetry trigger, not
            #     an rfaults injection point, so the per-fault flight
            #     accounting below must not expect a fault-fire dump)
            report["content_quality"] = await _content_breach_scenario(
                session, port, recovery_budget_s)

            # 5e) hostile-wire co-tenancy (ISSUE 18): a peer flooding
            #     spoofed acks + malformed JSON walks the ingress
            #     ladder to eviction (events + flight dump) while a
            #     legit co-tenant keeps streaming; component floods
            #     cover the NACK-amplification and malformed-SCTP
            #     vectors (separate report key like content_quality:
            #     not an rfaults injection point)
            report["hostile_client"] = await _hostile_client_scenario(
                session, port, frags, recovery_budget_s)

            # 5f) damage plane (ISSUE 20): a calm desktop spiking to a
            #     full-frame change must emit damage_spike (events +
            #     flight dump with the content block), ride the
            #     capacity charge to full cost, and never disturb the
            #     serving co-tenant (separate report key like
            #     content_quality: not an rfaults injection point)
            report["damage_spike"] = await _damage_spike_scenario(
                session, port, frags, recovery_budget_s)

            # 6) RTCP loss burst + sustained budget breach -> the
            #    degradation ladder engages, then restores
            report["degrade"] = await _degrade_scenario(
                cfg, session, recovery_budget_s)

            # 6b) sustained bandwidth cap -> REMB-driven ladder
            #     downshift and restore (the forward congestion signal)
            report["degrade"]["remb_cap"] = \
                await _remb_cap_scenario(cfg, session,
                                         recovery_budget_s)
            report["faults"]["peer_rtcp_loss_burst"] = {
                "fired": report["degrade"]["loss_burst"]["fired"],
                "recovered": report["degrade"]["loss_burst"]["recovered"],
                "recovery_ms":
                    report["degrade"]["loss_burst"]["recovery_ms"],
            }

        if continuity or continuity_only:
            # 7) device preemption mid-GOP -> checkpoint restore on a
            #    re-acquired device, same SSRC/seq/timestamp lineage
            report["continuity"]["device_preempt"] = \
                await _device_preempt_scenario(session, recovery_budget_s)

            # 8) mesh chip lost -> N->N-1 re-bucket, recovery IDR on
            #    every surviving session
            report["continuity"]["mesh_chip_lost"] = \
                await _mesh_failover_scenario(quick, recovery_budget_s,
                                              timeout_s * 0.5)

            # 9) rolling restart -> drain-to-migrate handoff (ISSUE 19):
            #    the successor adopts the spooled snapshot and the
            #    client resumes on the same SSRC with contiguous seq,
            #    exactly one recovery IDR and zero sheds (no "fired"
            #    key: not an rfaults injection point, so the per-fault
            #    flight accounting skips it)
            report["continuity"]["rolling_restart"] = \
                await _rolling_restart_scenario(recovery_budget_s,
                                                timeout_s * 0.5)

        # /metrics must carry the transitions (acceptance criterion)
        import aiohttp

        async with aiohttp.ClientSession() as http:
            async with http.get(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                text = await resp.text()
        report["metrics_visible"] = (
            "dngd_fault_injections_total" in text
            and (continuity_only
                 or ("dngd_degrade_step" in text
                     and "dngd_degrade_transitions_total" in text
                     and "dngd_sctp_retransmits_total" in text
                     and "dngd_rtx_packets_total" in text
                     and "dngd_nack_received_total" in text
                     and "dngd_idr_requests_total" in text
                     and "dngd_content_psnr_db" in text
                     and "dngd_content_damage_fraction" in text
                     and "dngd_ingress_violations_total" in text
                     and "dngd_ingress_peers" in text))
            and (not (continuity or continuity_only)
                 or "dngd_session_recoveries_total" in text))
    finally:
        rfaults.disarm_all()
        drain.cancel()
        session.close()
        await runner.cleanup()

    report["wall_s"] = round(time.perf_counter() - t_start, 2)

    # -- flight-recorder assertions (ISSUE 13 acceptance) --------------
    # every fault point that actually FIRED must have produced at least
    # one dump, and the continuity faults' dumps must carry the
    # postmortem payload (journeys + the triggering event + the budget)
    obsf.FLIGHT.flush_spool()
    by_reason = obsf.FLIGHT.by_reason()
    fired_points = [k for k, v in report["faults"].items()
                    if v.get("fired")]
    fired_points += [k for k, v in report["continuity"].items()
                     if v.get("fired")]
    per_fault = {pt: by_reason.get(f"fault-fire:{pt}", 0)
                 for pt in fired_points}
    content_ok: dict = {}
    for pt in ("device_preempt", "mesh_chip_lost"):
        if report["continuity"].get(pt, {}).get("fired"):
            dump = obsf.FLIGHT.find_dump("fault-fire", pt)
            content_ok[pt] = bool(
                dump
                and dump.get("journeys")
                and any(j for j in dump["journeys"].values())
                and any(e.get("kind") == "fault-fire"
                        and e.get("point") == pt
                        for e in dump.get("events", ()))
                and dump.get("budget"))
    report["flight"] = {
        "dumps_total": sum(by_reason.values()),
        "by_reason": by_reason,
        "spool_dir": obsf.FLIGHT.spool_dir(),
        "per_fault": per_fault,
        "content_ok": content_ok,
        "ok": (bool(per_fault)
               and all(n >= 1 for n in per_fault.values())
               and all(content_ok.values())),
    }

    cont_ok = all(
        c.get("recovered") for c in report["continuity"].values()
        if c.get("recovered") is not None)     # skipped scenarios pass
    if continuity_only:
        report["all_recovered"] = (cont_ok
                                   and report.get("metrics_visible", False)
                                   and report["flight"]["ok"])
    else:
        report["all_recovered"] = (
            all(f.get("recovered") for f in report["faults"].values())
            and report.get("content_quality", {}).get("recovered", False)
            and report.get("hostile_client", {}).get("recovered", False)
            and report.get("damage_spike", {}).get("recovered", False)
            and report["degrade"].get("breach", {}).get("recovered", False)
            and report["degrade"].get("remb_cap", {}).get("recovered",
                                                          False)
            and cont_ok
            and report.get("metrics_visible", False)
            and report["flight"]["ok"])
    return report


async def _ws_stall_scenario(cfg, session, port, frags,
                             recovery_budget_s: float) -> dict:
    """A stalled websocket client is evicted; the session keeps serving
    everyone else and the evicted client reconnects cleanly."""
    import aiohttp

    from .session import SubscriberSet

    t0 = time.perf_counter()
    evicted = False
    reconnected = False
    fired = 0
    async with aiohttp.ClientSession() as http:
        async with http.ws_connect(f"http://127.0.0.1:{port}/ws",
                                   max_msg_size=0) as ws:
            await ws.receive_json(timeout=recovery_budget_s)   # hello
            # a truly wedged client drains (essentially) nothing: the
            # stall must be long relative to the publish rate, or each
            # drained item frees a slot and resets the slow streak
            stall_fires = SubscriberSet.SLOW_EVICT_STREAK + 40
            rfaults.arm("ws_send_stall", count=stall_fires,
                        delay_ms=5000.0)
            deadline = time.perf_counter() + recovery_budget_s * 2
            while time.perf_counter() < deadline:
                msg = await ws.receive(
                    timeout=max(0.1, deadline - time.perf_counter()))
                if msg.type == aiohttp.WSMsgType.TEXT \
                        and '"evicted"' in msg.data:
                    evicted = True
                    break
                if msg.type in (aiohttp.WSMsgType.CLOSED,
                                aiohttp.WSMsgType.CLOSE,
                                aiohttp.WSMsgType.ERROR):
                    break
        fired = stall_fires - rfaults.armed_count("ws_send_stall")
        rfaults.disarm("ws_send_stall")
        # reconnect grace: the same client re-joins immediately
        async with http.ws_connect(f"http://127.0.0.1:{port}/ws",
                                   max_msg_size=0) as ws2:
            hello = await ws2.receive_json(timeout=recovery_budget_s)
            reconnected = hello.get("type") == "hello"
    # the in-process subscriber must have kept flowing throughout
    flowing = await _await_frag(frags, time.perf_counter(),
                                recovery_budget_s)
    return {"fired": fired,
            "recovered": bool(evicted and reconnected
                              and flowing is not None),
            "evicted": evicted, "reconnected": reconnected,
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1)}


async def _degrade_scenario(cfg, session,
                            recovery_budget_s: float) -> dict:
    """Drive the degradation ladder with a fast-tick controller bound to
    the live session: an RTCP loss burst engages it, a sustained
    collect-stage breach walks it further down, and both restore."""
    ctl = DegradeController(
        SessionExecutor(session, cfg=cfg),
        window=60, min_frames=8, breach_ticks=2, recover_ticks=3,
        cooldown_s=0.1,
        # qp/fps only under --quick-ish budgets: the res_down rung
        # recompiles a fresh geometry, which the full run exercises via
        # the dynamic-resize path already covered by tier-1 tests
        max_level=3)
    out = {"ladder": [s.name for s in ctl.steps]}

    async def tick_until(pred, budget_s: float) -> bool:
        deadline = time.perf_counter() + budget_s
        while time.perf_counter() < deadline:
            ctl.tick()
            if pred():
                return True
            await asyncio.sleep(0.1)
        return False

    try:
        # Calibrate the budget to the ORGANIC baseline of this host: a
        # loaded CI box may serve the tiny geometry slower than the
        # absolute rung budget, and that steady state must not read as
        # a breach — the scenario tests the ladder's REACTION to an
        # injected regression, not the host's absolute speed.
        deadline = time.perf_counter() + recovery_budget_s
        while ctl.p50_ms() is None and time.perf_counter() < deadline:
            await asyncio.sleep(0.1)
        organic = ctl.p50_ms() or 0.0
        budget = max(ctl.budget_ms() or 1000.0 / max(cfg.refresh, 1),
                     organic * 3.0)
        ctl.set_budget_ms(budget)
        out["organic_p50_ms"] = round(organic, 1)
        out["budget_ms"] = round(budget, 1)

        # -- loss burst: engage at least the first rung ---------------
        burst = 400
        rfaults.arm("peer_rtcp_loss_burst", count=burst)
        t0 = time.perf_counter()
        engaged = await tick_until(lambda: ctl.level > 0,
                                   recovery_budget_s)
        fired = burst - rfaults.armed_count("peer_rtcp_loss_burst")
        rfaults.disarm("peer_rtcp_loss_burst")
        restored = await tick_until(lambda: ctl.level == 0,
                                    recovery_budget_s)
        out["loss_burst"] = {
            "fired": fired, "engaged": engaged,
            "recovered": bool(engaged and restored),
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1)}

        # -- sustained budget breach: collect stage inflated past the
        #    calibrated budget until the ladder sheds quality ----------
        rfaults.arm("collect_timeout", count=100000, mode="slow",
                    delay_ms=budget * 3.0)
        t0 = time.perf_counter()
        max_level = 0

        def note_level():
            nonlocal max_level
            max_level = max(max_level, ctl.level)
            return ctl.level >= min(2, len(ctl.steps))

        engaged = await tick_until(note_level, recovery_budget_s * 2)
        rfaults.disarm("collect_timeout")
        restored = await tick_until(lambda: ctl.level == 0,
                                    recovery_budget_s * 2)
        out["breach"] = {
            "engaged": engaged, "max_level": max_level,
            "recovered": bool(engaged and restored),
            "recovery_ms": round((time.perf_counter() - t0) * 1e3, 1)}
        out["transitions"] = ctl.transitions
    finally:
        ctl.stop()
        # belt and braces: whatever the scenario left engaged, undo
        session.set_qp_offset(0)
        session.set_fps_cap(None)
    return out

async def _hostile_client_scenario(session, port, frags,
                                   recovery_budget_s: float) -> dict:
    """Hostile-wire co-tenancy (ISSUE 18 acceptance): one /ws peer
    floods spoofed journey acks and malformed control JSON until the
    ingress governor walks it WARN -> QUARANTINE -> EVICT (both rungs
    visible at /debug/events, the eviction with a flight-recorder dump
    through the shed path), while a LEGIT co-tenant on the same session
    keeps receiving media with its real fprobe acks accepted the whole
    time.  Component floods cover the media-plane vectors a loopback ws
    client cannot carry: a NACK storm against the RTCP monitor (17x BLP
    amplification capped by the per-peer budget) and a malformed-SCTP
    barrage that must neither raise nor grow the reassembly buffer."""
    import aiohttp

    from ..obs import flight as obsf
    from ..resilience import ingress as ringress
    from ..webrtc import rtcp as rtcp_mod
    from ..webrtc import sctp as sctp_mod

    t0 = time.perf_counter()
    out: dict = {}
    legit = {"frames": 0, "acks": 0, "evicted": False, "err": None}
    stop = asyncio.Event()

    async def legit_client(http) -> None:
        try:
            async with http.ws_connect(f"http://127.0.0.1:{port}/ws",
                                       max_msg_size=0) as ws:
                while not stop.is_set():
                    msg = await ws.receive(timeout=recovery_budget_s)
                    if msg.type == aiohttp.WSMsgType.BINARY:
                        legit["frames"] += 1
                    elif msg.type == aiohttp.WSMsgType.TEXT:
                        if '"evicted"' in msg.data or '"shed"' in msg.data:
                            legit["evicted"] = True
                            return
                        try:
                            ctrl = json.loads(msg.data)
                        except ValueError:
                            continue
                        if ctrl.get("type") == "fprobe":
                            # the honest ack path: echo the REAL fid
                            await ws.send_json(
                                {"type": "ack", "id": ctrl["id"]})
                            legit["acks"] += 1
                    elif msg.type in (aiohttp.WSMsgType.CLOSED,
                                      aiohttp.WSMsgType.CLOSE,
                                      aiohttp.WSMsgType.ERROR):
                        legit["evicted"] = True
                        return
        except Exception as e:          # noqa: BLE001 - reported below
            legit["err"] = repr(e)

    hostile = {"sent": 0, "shed_seen": False, "closed": False}

    async def hostile_reader(ws) -> None:
        try:
            while True:
                msg = await ws.receive(timeout=recovery_budget_s)
                if msg.type == aiohttp.WSMsgType.TEXT \
                        and '"shed"' in msg.data:
                    hostile["shed_seen"] = True
                elif msg.type in (aiohttp.WSMsgType.CLOSED,
                                  aiohttp.WSMsgType.CLOSE,
                                  aiohttp.WSMsgType.ERROR):
                    hostile["closed"] = True
                    return
        except (asyncio.TimeoutError, Exception):  # noqa: BLE001
            hostile["closed"] = True

    async with aiohttp.ClientSession() as http:
        legit_task = asyncio.ensure_future(legit_client(http))
        # let the legit client settle into the media flow first
        deadline = time.perf_counter() + recovery_budget_s
        while legit["frames"] < 3 and time.perf_counter() < deadline:
            await asyncio.sleep(0.05)
        frames_before = legit["frames"]

        async with http.ws_connect(f"http://127.0.0.1:{port}/ws",
                                   max_msg_size=0) as ws:
            reader = asyncio.ensure_future(hostile_reader(ws))
            try:
                # alternate spoofed acks (never-issued fids) with
                # malformed JSON; the flood deliberately overruns the
                # signal budget and then hammers through quarantine,
                # which is what walks the score to the evict rung
                for i in range(600):
                    if hostile["shed_seen"] or hostile["closed"]:
                        break
                    if i % 2:
                        await ws.send_str('{"type": "ack", "id": '
                                          + str(10 ** 9 + i) + "}")
                    else:
                        await ws.send_str('{"broken json %d' % i)
                    hostile["sent"] += 1
                    if i % 50 == 49:
                        # pace the flood against the media clock: the
                        # isolation claim is "legit frames keep landing
                        # WHILE the hostile peer hammers", so until the
                        # legit client makes progress each burst yields
                        # long enough for a frame interval to elapse —
                        # otherwise a cold pipeline can outlast a
                        # wall-clock-instant flood and the during-flood
                        # check races the first encode
                        burst_deadline = time.perf_counter() + 1.5
                        while legit["frames"] <= frames_before \
                                and time.perf_counter() < burst_deadline \
                                and not (hostile["shed_seen"]
                                         or hostile["closed"]):
                            await asyncio.sleep(0.05)
                        await asyncio.sleep(0)   # let the server run
                evict_deadline = time.perf_counter() + recovery_budget_s
                while not (hostile["shed_seen"] or hostile["closed"]) \
                        and time.perf_counter() < evict_deadline:
                    await asyncio.sleep(0.05)
            except (ConnectionResetError, RuntimeError):
                hostile["closed"] = True         # server closed mid-send
            finally:
                if not reader.done():
                    await asyncio.sleep(0.2)
                reader.cancel()

        frames_after_flood = legit["frames"]
        # the co-tenant must keep flowing AFTER the hostile eviction too
        flow_deadline = time.perf_counter() + recovery_budget_s
        while legit["frames"] <= frames_after_flood \
                and time.perf_counter() < flow_deadline:
            await asyncio.sleep(0.05)
        stop.set()
        await asyncio.wait_for(legit_task, recovery_budget_s)

        # ladder rungs must be CLIENT-visible on the fleet timeline,
        # and the boot-registered metric families must carry the counts
        async with http.get(
                f"http://127.0.0.1:{port}/debug/events") as resp:
            events_text = await resp.text()
        async with http.get(
                f"http://127.0.0.1:{port}/metrics") as resp:
            metrics_text = await resp.text()

    dump = obsf.FLIGHT.find_dump("shed", "ingress_evict")
    out["live"] = {
        "hostile_sent": hostile["sent"],
        "hostile_evicted": bool(hostile["shed_seen"]
                                or hostile["closed"]),
        "quarantine_visible": "ingress_quarantine" in events_text,
        "evict_visible": "ingress_evict" in events_text,
        "flight_dump": bool(dump),
        "violations_on_metrics":
            'dngd_ingress_violations_total{reason="ack_spoof"}'
            in metrics_text,
        "legit_frames": legit["frames"],
        "legit_acks": legit["acks"],
        "legit_flow_during_flood": frames_after_flood > frames_before,
        "legit_flow_after_evict": legit["frames"] > frames_after_flood,
        "legit_survived": not legit["evicted"] and legit["err"] is None,
    }

    # -- component: NACK storm against the RTCP monitor ----------------
    nack_budget = ringress.PeerBudget("hostile-nack")
    mon = rtcp_mod.PeerRtcpMonitor({0x1111: ("video", 90_000)})
    mon.budget = nack_budget
    delivered = []
    mon.on_nack = lambda kind, seqs: delivered.extend(seqs)
    try:
        media = struct.pack(">I", 0x1111)
        for i in range(200):
            # one FCI, full BLP: 17 expanded seqs per 16-byte packet
            pkt = (struct.pack(">BBH", 0x81, 205, 3)
                   + struct.pack(">I", 0xABAD1DEA) + media
                   + struct.pack(">HH", (i * 17) & 0xFFFF, 0xFFFF))
            mon.ingest(pkt)
        burst = max(ringress._RATE_KINDS["nack"][1] * 2.0, 10.0)
        out["nack_flood"] = {
            "sent_seqs": 200 * 17,
            "delivered_seqs": len(delivered),
            "capped": len(delivered) <= burst + 50,
        }
    finally:
        nack_budget.close()
        mon.close()

    # -- component: malformed-SCTP barrage -----------------------------
    # an ESTABLISHED association (matching vtag), so lying chunk
    # headers reach the chunk parser instead of the vtag drop
    sctp_budget = ringress.PeerBudget("hostile-sctp")
    to_srv: list = []
    to_cli: list = []
    assoc = sctp_mod.SctpAssociation(role="server",
                                     on_transmit=to_cli.append)
    cli = sctp_mod.SctpAssociation(role="client",
                                   on_transmit=to_srv.append)
    cli.connect()
    for _ in range(8):
        for pkt in to_srv:
            assoc.receive(pkt)
        to_srv.clear()
        for pkt in to_cli:
            cli.receive(pkt)
        to_cli.clear()
        if assoc.established and cli.established:
            break
    assoc.budget = sctp_budget
    vtag = assoc.local_tag
    try:
        violations0 = ringress._M_VIOLATIONS.labels(
            "sctp_malformed_chunk").value
        for i in range(300):
            kind = i % 3
            if kind == 0:                  # pure garbage
                pkt = bytes((i * 7 + j) & 0xFF for j in range(48))
            elif kind == 1:                # valid header, bad CRC
                pkt = (struct.pack(">HHI", 5000, 5000, vtag)
                       + b"\xff\xff\xff\xff"
                       + struct.pack(">BBH", 0, 3, 32) + b"x" * 28)
            else:                          # truncated DATA value: valid
                # framing + CRC, but too short for the chunk's own
                # fixed fields — the in-handler malformed path
                pkt = sctp_mod.pack_packet(
                    5000, 5000, vtag,
                    [sctp_mod.pack_chunk(sctp_mod.CT_DATA, 3, b"xx")])
            assoc.receive(pkt)
        out["sctp_malformed"] = {
            "sent": 300,
            "established": bool(assoc.established),
            "no_raise": True,
            "buf_bounded": assoc._rcv_buf_bytes <= assoc._rcv_buf_cap,
            "scored": ringress._M_VIOLATIONS.labels(
                "sctp_malformed_chunk").value > violations0,
            "governor_state": sctp_budget.state,
        }
    finally:
        sctp_budget.close()
        assoc._close("hostile barrage done")
        cli._close("hostile barrage done")

    live = out["live"]
    out["recovered"] = bool(
        live["hostile_evicted"]
        and live["quarantine_visible"] and live["evict_visible"]
        and live["flight_dump"] and live["violations_on_metrics"]
        and live["legit_survived"] and live["legit_flow_during_flood"]
        and live["legit_flow_after_evict"] and live["legit_acks"] >= 1
        and out["nack_flood"]["capped"]
        and out["sctp_malformed"]["no_raise"]
        and out["sctp_malformed"]["buf_bounded"]
        and out["sctp_malformed"]["scored"])
    out["recovery_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    return out
