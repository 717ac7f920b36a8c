"""Stock-selkies web-client signaling compatibility shim.

SURVEY §2.2 E2 set "behavior-compatible with the selkies web client"
as the rebuild bar; the first-party client speaks its own (simpler)
protocol.  This adapter translates the selkies-gstreamer signaling
schema onto the existing session machinery so an UNMODIFIED selkies
web app can negotiate and stream (VERDICT r4 item 10; the web app the
reference actually serves, reference
selkies-gstreamer-entrypoint.sh:43-47):

  client -> ``HELLO <peer_id> <btoa(meta)>``     server -> ``HELLO``
  server -> ``{"sdp": {"type": "offer", ...}}``  (role inversion: the
            selkies APP's webrtcbin creates the offer — see
            WebRtcPeer.create_offer)
  client -> ``{"sdp": {"type": "answer", ...}}``
  client -> ``{"ice": {"candidate": ...}}``      (trickle; feeds TURN
            permissions — our ICE-lite learns the pair from checks)
  server -> ``{"ice": ...}`` never sent (candidates ride the offer,
            which ends with a=end-of-candidates)

Mounted at ``/<app>/signalling/`` for any app name plus the literal
``/signalling`` (the stock client derives the path from its app name).

Input: selkies carries input/clipboard/stats over SCTP data channels on
the media DTLS association.  The offer negotiates
``m=application webrtc-datachannel`` (webrtc/sdp.build_offer), the
first-party SCTP/DCEP stack (webrtc/sctp + webrtc/datachannel)
terminates the channels, and :func:`attach_input_channels` routes their
messages into the same CSV parser and X injection path the WebSocket
input uses (web/input) — an unmodified selkies client's keystrokes land
on the desktop byte-for-byte identically to the first-party client's.
"""

from __future__ import annotations

import json
import logging

from aiohttp import WSMsgType, web

from ..obs import metrics as obsm
from ..resilience import ingress as ringress

log = logging.getLogger(__name__)

__all__ = ["register_selkies_routes", "attach_input_channels",
           "ingest_client_qoe", "drop_client_qoe"]

_M_INPUT_DROPPED = obsm.counter(
    "dngd_datachannel_input_dropped_total",
    "Channel input messages dropped by the bounded per-peer queue")

# -- client-side QoE (ISSUE 17 satellite): the decode half of
# glass-to-glass.  The stock selkies HUD (and the first-party client)
# can push periodic reports over the stats channel; whatever of the
# rendered-fps / decode-time / jitter-buffer trio a client reports
# lands on per-peer gauges next to the server-side content plane.
_M_QOE = obsm.gauge(
    "dngd_client_qoe",
    "Client-reported playback QoE over the stats data channel "
    "(stat=fps|decode_ms|jitter_buffer_ms)", ("peer", "stat"))
_M_QOE_REPORTS = obsm.counter(
    "dngd_client_qoe_reports_total",
    "Client QoE reports ingested from the stats data channel",
    ("peer",))

# tolerant field map: selkies-gstreamer HUD names, webrtc getStats
# names, and the obvious snake_case spellings all land on one stat
_QOE_FIELDS = {
    "fps": ("fps", "framerate", "framespersecond", "renderedfps",
            "framesperseconddecoded", "frameratedecoded"),
    "decode_ms": ("decode_ms", "decodetime", "decodetimems",
                  "framedecodetime", "videodecodetime"),
    "jitter_buffer_ms": ("jitter_buffer_ms", "jitterbuffer",
                         "jitterbufferms", "jitterbufferdelay",
                         "jitterbufferdelayms"),
}


def _qoe_scan(obj, found: dict, depth: int = 0) -> None:
    """Collect recognized QoE numbers from a (possibly nested) report."""
    if depth > 2 or not isinstance(obj, dict):
        return
    for k, v in obj.items():
        if isinstance(v, dict):
            _qoe_scan(v, found, depth + 1)
            continue
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            continue
        key = str(k).replace("_", "").replace("-", "").lower()
        for stat, names in _QOE_FIELDS.items():
            if key in names and stat not in found:
                try:
                    found[stat] = float(v)
                except OverflowError:
                    # JSON ints are arbitrary precision; a 10**400
                    # "fps" must land as a droppable non-finite, not
                    # an uncaught raise in the channel callback
                    found[stat] = float("inf")


# sane-range clamps for client-reported numbers (ISSUE 18 satellite:
# the client is untrusted — an absurd report must not poison the QoE
# dashboards the fleet plane reads next to the server-side content
# stats).  Values clamp into range; non-finite values drop.
_QOE_CLAMPS = {
    "fps": (0.0, 1000.0),
    "decode_ms": (0.0, 10_000.0),
    "jitter_buffer_ms": (0.0, 10_000.0),
}
# bound the per-peer label population independently of the registry's
# global cardinality cap: past this many distinct reporting peers, new
# ones collapse onto one "other" series instead of minting their own
_QOE_PEER_CAP = 32
_qoe_peer_names: set = set()


def ingest_client_qoe(peer_name: str, msg, budget=None) -> bool:
    """Ingest one stats-channel message's QoE fields into the per-peer
    gauges; returns True when the message carried any (i.e. it was a
    client report, not a HUD poll).  ``budget`` (resilience/ingress)
    rate-limits reports and scores out-of-range values."""
    found: dict = {}
    _qoe_scan(msg, found)
    if not found:
        return False
    if budget is not None and (not budget.allow_nonmedia()
                               or not budget.charge("qoe")):
        return True          # it WAS a QoE report; it just doesn't land
    if peer_name not in _qoe_peer_names:
        if len(_qoe_peer_names) >= _QOE_PEER_CAP:
            peer_name = "other"
        else:
            _qoe_peer_names.add(peer_name)
    for stat, v in found.items():
        lo, hi = _QOE_CLAMPS.get(stat, (0.0, 1e6))
        if not (v == v and -1e18 < v < 1e18):     # NaN / inf
            if budget is not None:
                budget.violation("qoe_insane", weight=0.5)
            continue
        if v < lo or v > hi:
            if budget is not None:
                budget.violation("qoe_insane", weight=0.25)
            v = min(max(v, lo), hi)
        _M_QOE.labels(peer_name, stat).set(v)
    _M_QOE_REPORTS.labels(peer_name).inc()
    return True


def drop_client_qoe(peer_name: str) -> None:
    """Peer teardown: stale per-peer QoE series must not outlive the
    connection (metrics cardinality contract)."""
    for stat in _QOE_FIELDS:
        _M_QOE.remove(peer_name, stat)
    _M_QOE_REPORTS.remove(peer_name)
    _qoe_peer_names.discard(peer_name)

# A flooding client must cost a counter bump, not unbounded memory: the
# /ws path gets natural backpressure from its sequential read loop; the
# channel path bounds its queue instead (injection drains via a
# subprocess-speed executor, so depth = seconds of typing burst).
INPUT_QUEUE_DEPTH = 1024


def attach_input_channels(peer, session, injector, loop=None) -> None:
    """Bind the selkies data channels on ``peer``.

    - ``input`` (and any unrecognized label — selkies multiplexes its
      whole control plane over one channel): each string message is one
      compact CSV input event, fed through the SAME parser + executor-
      offloaded injection path as the WebSocket input
      (server.handle_input_text), so the two transports are
      byte-for-byte identical at the X boundary;
    - ``clipboard``: raw base64 text -> bounded clipboard set (reuses
      the parser's ``c,`` op and its hardening caps);
    - ``stats``: any message answers with the live session stats JSON
      (the selkies HUD poll).
    """
    import asyncio

    from .server import handle_input_text, spawn_bg

    # One serialized worker per peer: channel callbacks enqueue, a
    # single consumer injects — keystroke ORDER is part of the input
    # contract, and concurrent executor hops would race it.  The worker
    # spawns lazily on the first channel and dies with the peer (the
    # close hook cancels it; tasks are strong-ref'd via spawn_bg).
    state = {"queue": None, "task": None}

    def _enqueue(text: str) -> None:
        if state["queue"] is None:
            state["queue"] = asyncio.Queue(maxsize=INPUT_QUEUE_DEPTH)

            async def worker():
                try:
                    while True:
                        t = await state["queue"].get()
                        try:
                            await handle_input_text(t, session,
                                                    injector, loop)
                        except Exception:
                            # a wedged backend (xdotool TimeoutExpired)
                            # must cost one event, not kill the worker
                            # and silently deaden input for the session
                            log.exception("channel input injection "
                                          "failed; message dropped")
                except asyncio.CancelledError:
                    pass

            state["task"] = spawn_bg(worker())
            hooks = getattr(peer, "close_hooks", None)
            if hooks is not None:
                hooks.append(state["task"].cancel)
        try:
            state["queue"].put_nowait(text)
        except asyncio.QueueFull:
            # drop-and-count, like the parser's hardening: newest lost
            # under flood beats unbounded growth (a real typist cannot
            # outrun a 1024-deep queue)
            _M_INPUT_DROPPED.inc()

    # the WS handler routes its input through the SAME worker once a
    # peer is bound (server._handle_client_msg): events spanning the
    # WS -> data-channel switchover (a drag whose press went over /ws
    # and release over the channel) must not be injected by two
    # concurrent executor hops in arbitrary order
    peer.input_enqueue = _enqueue

    peer_name = str(getattr(peer, "peer_id", "")
                    or f"peer-{id(peer) & 0xffffff:x}")
    hooks0 = getattr(peer, "close_hooks", None)
    if hooks0 is not None:
        hooks0.append(lambda: drop_client_qoe(peer_name))

    def on_channel(channel) -> None:
        label = (channel.label or "").lower()

        if label.startswith("stats"):
            def on_stats(_data, _ch=channel):
                try:
                    text = (_data if isinstance(_data, str)
                            else _data.decode("utf-8", "replace"))
                    # first-party glass-to-glass ack over the stats
                    # channel: {"type": "ack", "frame_id"|"id": N}
                    # closes the frame's journey at server receipt
                    # (obs/journey); a client QoE report (rendered
                    # fps / decode time / jitter-buffer delay) feeds
                    # the per-peer dngd_client_qoe gauges; anything
                    # else is the selkies HUD poll and gets the live
                    # stats JSON back
                    if text.startswith("{"):
                        try:
                            msg = json.loads(text)
                        except ValueError:
                            msg = None
                        budget = getattr(peer, "ingress_budget", None)
                        if msg and msg.get("type") == "ack":
                            # same gating as the /ws ack path: only a
                            # fid from THIS connection's outstanding
                            # probe window may close a journey —
                            # spoofed/replayed ids are violations, not
                            # fabricated g2g samples
                            if budget is not None and \
                                    not budget.charge("ack"):
                                return
                            try:
                                fid = int(msg.get("frame_id",
                                                  msg.get("id")) or 0)
                            except (TypeError, ValueError):
                                if budget is not None:
                                    budget.violation("ack_spoof",
                                                     weight=0.5)
                                return
                            probes = getattr(peer, "ingress_probes",
                                             None)
                            if probes is not None and \
                                    not probes.take(fid):
                                if budget is not None:
                                    budget.violation("ack_spoof",
                                                     weight=0.5)
                                return
                            book = getattr(session, "journeys", None)
                            if book is not None:
                                book.close(fid, method="client")
                            return
                        if msg and ingest_client_qoe(peer_name, msg,
                                                     budget=budget):
                            return
                    payload = (session.stats_summary()
                               if hasattr(session, "stats_summary")
                               else {})
                    _ch.send(json.dumps({"type": "stats",
                                         "data": payload}))
                except Exception:
                    log.exception("stats channel reply failed")

            channel.on_message = on_stats
            return

        if label.startswith("clipboard"):
            def on_clip(data):
                text = (data if isinstance(data, str)
                        else data.decode("utf-8", "replace"))
                _enqueue(f"c,{text}")

            channel.on_message = on_clip
            return

        # "input" and anything else: the CSV input protocol
        def on_input(data):
            text = (data if isinstance(data, str)
                    else data.decode("utf-8", "replace"))
            _enqueue(text)

        channel.on_message = on_input

    peer.on_datachannel = on_channel


async def _signalling_handler(request: web.Request, session, audio,
                              conn_turn, advertise_ip: str,
                              injector=None):
    import asyncio

    ws = web.WebSocketResponse(heartbeat=20.0, max_msg_size=0)
    await ws.prepare(request)
    loop = asyncio.get_running_loop()
    peer = None
    on_au = on_audio = None
    negotiated = False
    # zero-downtime handoff (resilience/handoff): same contract as /ws —
    # a ?resume= token redeems the predecessor's wire continuity, and
    # this connection registers for the NEXT migration.  The stock
    # protocol is untouched; the token and migrate notice ride shim-only
    # JSON keys ({"resume": ...} / {"migrate": ...}) a stock client
    # ignores and a shim-aware client honors.
    hmgr = request.app.get("handoff")
    resume_entry = None
    handoff_token = None
    if hmgr is not None and hmgr.enabled:
        tok = request.query.get("resume")
        if tok:
            resume_entry = hmgr.claim(tok)

        def _notify_migrate(new_tok, retry_s, _ws=ws):
            async def _go():
                try:
                    await _ws.send_str(json.dumps(
                        {"migrate": {"resume": new_tok,
                                     "retry_after_s": round(retry_s,
                                                            2)}}))
                except Exception:
                    pass
            from .server import spawn_bg
            spawn_bg(_go())

        handoff_token = hmgr.register(
            sid=f"selkies-{request.remote or 'local'}",
            notify=_notify_migrate)
    # trust boundary (resilience/ingress): one governor + one probe
    # window per signalling connection, shared by every peer it
    # negotiates.  EVICT closes the socket with the selkies error shape.
    probes = ringress.ProbeWindow()

    def _ingress_evict(bud, reason, _ws=ws):
        async def _go():
            try:
                await _ws.send_str(json.dumps(
                    {"error": "evicted: protocol violations"}))
                await _ws.close()
            except Exception:
                pass
        from .server import spawn_bg
        spawn_bg(_go())

    budget = ringress.PeerBudget(
        f"selkies-{request.remote or 'local'}", on_evict=_ingress_evict)

    def teardown_peer():
        nonlocal peer, on_au, on_audio, negotiated
        if on_au is not None:
            session.remove_au_listener(on_au)
            on_au = None
        if on_audio is not None and audio is not None:
            audio.remove_listener(on_audio)
            on_audio = None
        if peer is not None:
            peer.close()
            peer = None
        negotiated = False

    try:
        async for msg in ws:
            if msg.type != WSMsgType.TEXT:
                if msg.type in (WSMsgType.CLOSE, WSMsgType.ERROR):
                    break
                continue
            text = msg.data
            if text.startswith("HELLO"):
                teardown_peer()      # a re-HELLO restarts negotiation
                await ws.send_str("HELLO")
                if handoff_token is not None:
                    # shim extension: the resume token for the NEXT
                    # process handoff (stock clients ignore it)
                    await ws.send_str(json.dumps(
                        {"resume": handoff_token}))
                # role inversion: WE offer now
                from ..webrtc.peer import WebRtcPeer

                codec_name = getattr(session, "codec_name", "")
                rtc_codec = ("H264" if codec_name.startswith("h264")
                             else "VP8" if codec_name.startswith("vp8")
                             else None)
                if rtc_codec is None or not hasattr(session,
                                                    "add_au_listener"):
                    await ws.send_str(json.dumps(
                        {"error": f"codec {codec_name!r} not "
                                  "RTC-streamable"}))
                    continue
                rtc_audio = (audio is not None
                             and getattr(audio, "format", "") == "opus")
                peer = WebRtcPeer(clock=getattr(session, "clock", None),
                                  video_codec=rtc_codec,
                                  sps=getattr(getattr(session, "muxer",
                                                      None), "sps", None),
                                  advertise_ip=advertise_ip,
                                  with_audio=rtc_audio,
                                  turn=conn_turn)
                # RTCP-fallback journey closure for the stock client
                peer.journeys = getattr(session, "journeys", None)
                peer.set_ingress_budget(budget)
                peer.ingress_probes = probes
                # stock-client PLI/FIR -> the session's rate-limited
                # IDR path (dedupes against the degrade ladder rung)
                from .session import keyframe_requester
                peer.on_keyframe_request = keyframe_requester(session)
                # bind input/clipboard/stats BEFORE any DCEP can arrive
                sess_injector = getattr(session, "injector", None) \
                    or injector
                attach_input_channels(peer, session, sess_injector,
                                      loop=loop)
                if resume_entry is not None and resume_entry.get("wire"):
                    # resumed client: the offer must carry the SSRCs it
                    # was already decoding on the predecessor
                    peer.import_wire(resume_entry["wire"])
                    resume_entry = None          # single-shot
                if handoff_token is not None and hmgr is not None:
                    hmgr.attach_wire(handoff_token, peer.export_wire)
                offer_sdp = await peer.create_offer()
                if request.remote:
                    await peer.add_remote_candidate_ip(request.remote)
                await ws.send_str(json.dumps(
                    {"sdp": {"type": "offer", "sdp": offer_sdp}}))
                continue
            if not text.startswith("{"):
                continue
            if not budget.allow_nonmedia():
                # flooding through the quarantine cooldown climbs the
                # ladder toward eviction (same contract as /ws)
                budget.violation("quarantine_ingest", weight=0.2)
                continue
            if not budget.charge("signal"):
                continue
            try:
                data = json.loads(text)
            except ValueError:
                budget.violation("signal_bad_json")
                continue
            if not isinstance(data, dict):
                budget.violation("signal_bad_json", weight=0.5)
                continue
            if "sdp" in data and peer is not None:
                sd = data["sdp"]
                if not isinstance(sd, dict):
                    budget.violation("signal_bad_json", weight=0.5)
                    continue
                if sd.get("type") == "answer" and not negotiated:
                    from ..webrtc.sdp import SdpError
                    try:
                        await peer.handle_answer(sd.get("sdp", ""))
                    except SdpError as e:
                        # hostile/corrupt answer: reject cleanly and
                        # leave the offer on the table for a retry
                        # instead of unwinding the whole /signalling
                        # handler
                        log.warning("answer rejected at trust "
                                    "boundary: %s (%s)", e.reason, e)
                        budget.violation(e.reason, weight=5.0)
                        await ws.send_str(json.dumps(
                            {"error": f"bad answer: {e.reason}"}))
                        continue
                    negotiated = True

                    def on_au(au, keyframe, pts, _p=peer):
                        _p.send_video_au(au, pts)

                    session.add_au_listener(on_au)
                    if (audio is not None
                            and getattr(audio, "format", "") == "opus"):
                        def on_audio(pts, packet, _p=peer):
                            _p.send_audio(packet, pts)

                        audio.add_listener(on_audio)
                    if hasattr(session, "request_keyframe"):
                        peer.on_ready = session.request_keyframe
            elif "ice" in data and peer is not None:
                cand = data["ice"] or {}
                line = cand.get("candidate", "") if isinstance(
                    cand, dict) else ""
                parts = line.split()
                if len(parts) >= 5:
                    await peer.add_remote_candidate_ip(parts[4])
    finally:
        if handoff_token is not None and hmgr is not None:
            hmgr.detach(handoff_token)
        teardown_peer()
        budget.close()
    return ws


def register_selkies_routes(app: web.Application, cfg, session,
                            audio, injector=None) -> None:
    """Mount the shim at /signalling and /{app}/signalling (both with
    and without trailing slash — the stock client builds the URL from
    its app name).  ``injector`` is the shared input path the data
    channels feed (falls back to ``session.injector`` per hub)."""
    from .turn import server_turn_config

    async def handler(request: web.Request):
        sockname = (request.transport.get_extra_info("sockname")
                    if request.transport is not None else None)
        advertise_ip = sockname[0] if sockname else "127.0.0.1"
        return await _signalling_handler(
            request, session, audio, server_turn_config(cfg),
            advertise_ip, injector=injector)

    app.router.add_get("/signalling", handler)
    app.router.add_get("/signalling/", handler)
    app.router.add_get("/{app_name}/signalling", handler)
    app.router.add_get("/{app_name}/signalling/", handler)
