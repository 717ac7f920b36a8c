"""One browser's WebRTC media session — the ``webrtcbin`` role.

Wiring: signaling delivers the browser's SDP offer; we answer ICE-lite +
DTLS-passive.  The browser's connectivity check validates the peer
address, its DTLS ClientHello drives the handshake through
``dtls.DtlsEndpoint``, the exported keys seed the SRTP contexts, and
from then on the TPU encoder's access units flow
``packetize -> protect -> UDP`` with periodic RTCP sender reports on the
shared :class:`..web.clock.MediaClock` for browser-side lip sync.

Reference parity map (selkies-gstreamer pipeline, SURVEY.md §3.2):
``rtph264pay`` -> rtp.packetize_h264, ``webrtcbin``'s ICE -> ice.py,
DTLS -> dtls.py, SRTP -> srtp.py, RTCP -> rtcp.py.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

from ..obs import metrics as obsm
from ..obs.trace import tracer
from ..web.clock import MediaClock
from ..web.mp4 import split_annexb
from . import feedback, rtcp, rtp, sdp
from .dtls import Certificate, DtlsEndpoint, generate_certificate
from .srtp import SrtpContext

log = logging.getLogger(__name__)

__all__ = ["WebRtcPeer", "process_certificate"]

_M_PKTS = obsm.counter(
    "dngd_webrtc_packets_sent_total",
    "SRTP media packets sent toward browsers", ("kind",))
_M_BYTES = obsm.counter(
    "dngd_webrtc_bytes_sent_total",
    "SRTP media payload bytes sent toward browsers", ("kind",))
_M_PEERS = obsm.gauge(
    "dngd_webrtc_peers", "Open WebRTC peer connections")

_CERT: Optional[Certificate] = None


def process_certificate() -> Certificate:
    """One self-signed cert per process (browser identity is per-session
    via ICE creds; regenerating per peer would just burn entropy)."""
    global _CERT
    if _CERT is None:
        _CERT = generate_certificate()
    return _CERT


class WebRtcPeer:
    """Sendonly video+audio toward one browser."""

    RTCP_INTERVAL_S = 1.0

    def __init__(self, clock: Optional[MediaClock] = None,
                 video_codec: str = "H264",
                 advertise_ip: str = "127.0.0.1",
                 certificate: Optional[Certificate] = None,
                 sps: Optional[bytes] = None,
                 with_audio: bool = True,
                 turn: Optional[dict] = None):
        from .ice import IceLiteEndpoint

        self.clock = clock if clock is not None else MediaClock()
        self.video_codec = video_codec
        # what the H.264 stream is negotiated as: Main when its SPS is
        self.h264_profile = sdp.h264_profile_level_id(sps)
        self.advertise_ip = advertise_ip
        self.with_audio = with_audio
        # {"host","port","username","credential"} -> allocate a relayed
        # candidate for OUR media (web/turn.server_turn_config)
        self.turn = turn
        # 64-bit unwrap of the 32-bit 90 kHz clock: the audio 48 kHz
        # rescale must not see the 2^32 wrap as a backwards jump
        self._pts_last: Optional[int] = None
        self._pts_acc = 0
        self.cert = certificate or process_certificate()
        self.ice = IceLiteEndpoint(on_dtls=self._on_dtls,
                                   on_rtp=self._on_rtp)
        self.dtls = DtlsEndpoint("server", certificate=self.cert)
        self.srtp_out: Optional[SrtpContext] = None
        self.srtp_in: Optional[SrtpContext] = None
        self.video = rtp.RtpStream(0, clock_rate=90_000)   # pt set by offer
        self.audio = rtp.RtpStream(0, clock_rate=48_000)
        self.ready: Optional[asyncio.Future] = None   # set in handle_offer
        self._offer: Optional[sdp.RemoteOffer] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._rtcp_task: Optional[asyncio.Task] = None
        self._timer_task: Optional[asyncio.Task] = None
        self.on_ready = None            # callback once SRTP is up
        # SCTP data channel plane (webrtc/sctp + datachannel): created
        # when the offer/answer negotiated m=application, activated on
        # DTLS completion.  on_datachannel fires per inbound DCEP OPEN.
        self.sctp = None                # SctpAssociation
        self.datachannels = None        # DataChannelEndpoint
        self.on_datachannel = None      # callback(DataChannel)
        self._sctp_remote_port: Optional[int] = None
        self._sctp_task: Optional[asyncio.Task] = None
        # run at close() — channel binders park their worker-teardown
        # here (web/selkies_shim.attach_input_channels)
        self.close_hooks: list = []
        # handoff continuity (resilience/handoff): wire state imported
        # before the offer; the SRTP/SCTP parts apply lazily because
        # those objects only exist after the DTLS handshake / offer
        self._pending_srtp_out: Optional[dict] = None
        self._pending_srtp_in: Optional[dict] = None
        self._pending_sctp: Optional[dict] = None
        # per-peer abuse governor (resilience/ingress), owned by the
        # signaling connection; set via set_ingress_budget so it fans
        # out to every untrusted decode plane this peer terminates
        self.ingress_budget = None
        self._closed = False
        # inbound RRs -> per-peer RTT/jitter/loss gauges (rtcp.py; kept
        # crypto-free so the RR path is testable without DTLS)
        self.rtcp_monitor = rtcp.PeerRtcpMonitor({
            self.video.ssrc: ("video", 90_000),
            self.audio.ssrc: ("audio", 48_000)})
        # glass-to-glass closure (obs/journey): the session's journey
        # book, set by whoever binds this peer to a session.  The log
        # maps each video frame's LAST absolute packet index -> pts so
        # an RR's extended-highest-seq closes every fully-received
        # frame's journey (the stock-client fallback when no ack
        # channel exists); 16-bit-wrap-safe (webrtc/feedback).
        self.journeys = None
        self._frame_log = feedback.FrameSeqLog(self.video.seq)
        self.rtcp_monitor.on_block = self._on_rr_block
        # loss-recovery plane (webrtc/feedback): send-history ring +
        # pacer on the way out; NACK->RTX, PLI/FIR->rate-limited IDR,
        # REMB->headroom gauge on the way back.  RTX activates only
        # when negotiated (handle_offer/handle_answer).
        self.pacer = feedback.Pacer(self._transmit_video)
        self.video_fb = feedback.FeedbackPlane(
            self.video, self._transmit_video, pacer=self.pacer,
            on_keyframe_request=self._keyframe_requested)
        # fn(reason) — the server wires the session's rate-limited
        # request_idr here so PLI/FIR dedupe against the degrade
        # ladder's IDR rung and the collect-failure resync
        self.on_keyframe_request = None
        self.rtcp_monitor.on_nack = self._on_nack
        self.rtcp_monitor.on_pli = self._on_pli
        self.rtcp_monitor.on_remb = self._on_remb
        # hot-path children resolved once; sends are integer adds
        self._m_vpkts = _M_PKTS.labels("video")
        self._m_vbytes = _M_BYTES.labels("video")
        self._m_apkts = _M_PKTS.labels("audio")
        self._m_abytes = _M_BYTES.labels("audio")
        self._tracer = tracer("webrtc")
        _M_PEERS.inc()

    def set_ingress_budget(self, budget) -> None:
        """Attach the connection's PeerBudget (resilience/ingress) to
        every untrusted decode plane: RTCP feedback now, SCTP/DCEP when
        :meth:`_setup_datachannels` creates them."""
        self.ingress_budget = budget
        self.rtcp_monitor.budget = budget
        if self.sctp is not None:
            self.sctp.budget = budget
        if self.datachannels is not None:
            self.datachannels.budget = budget

    # -- signaling -----------------------------------------------------

    async def handle_offer(self, offer_sdp: str) -> str:
        """Parse the browser's offer, bind the ICE socket, return the
        answer SDP."""
        self._loop = asyncio.get_running_loop()
        self.ready = self._loop.create_future()
        offer = sdp.parse_offer(offer_sdp, video_codec=self.video_codec,
                                h264_profile=self.h264_profile)
        self._offer = offer
        if not self.with_audio:
            # no RTC-feedable audio (e.g. AUDIO_CODEC=pcm): answer the
            # audio m-line inactive so the client keeps the /audio WS
            for m in offer.media:
                if m.kind == "audio":
                    m.payload_type = None
        for m in offer.media:
            if m.kind == "video" and m.payload_type is not None:
                self.video.pt = m.payload_type
                self._negotiate_feedback(m)
            elif m.kind == "audio" and m.payload_type is not None:
                self.audio.pt = m.payload_type
            elif m.kind == "application" and m.sctp_port is not None:
                self._sctp_remote_port = m.sctp_port
        self.ice.set_remote_credentials(offer.ice_ufrag, offer.ice_pwd)
        await self.ice.bind()
        self._timer_task = self._loop.create_task(self._dtls_timer())
        candidates = [self.ice.candidate_line(self.advertise_ip)]
        if self.turn:
            # Server-side relayed candidate (RFC 5766; reference
            # README.md:65-69 — TURN exists for deployments where the
            # host candidate is unreachable).  Failure is non-fatal:
            # the host candidate still goes out.
            await self._setup_turn_relay(candidates, offer.candidate_ips)
        ssrcs = {"video": self.video.ssrc, "audio": self.audio.ssrc}
        if self.video_fb.rtx is not None:
            ssrcs["video_rtx"] = self.video_fb.rtx.ssrc
        answer = sdp.build_answer(
            offer, self.ice.local_ufrag, self.ice.local_pwd,
            self.cert.fingerprint,
            candidates,
            self.advertise_ip,
            ssrcs=ssrcs,
            video_codec=self.video_codec, h264_profile=self.h264_profile)
        return answer

    def _negotiate_feedback(self, m: "sdp.MediaSection") -> None:
        """Arm the loss-recovery plane to what the peer's video section
        offered: NACK repair (RTX when an apt-mapped PT exists, verbatim
        resend otherwise) and PLI/FIR/REMB intake."""
        self.video_fb.nack_enabled = "nack" in m.feedback
        if self.video_fb.nack_enabled and m.rtx_payload_type is not None:
            prev = self.video_fb.rtx       # keep the SSRC we advertised
            self.video_fb.enable_rtx(
                m.rtx_payload_type,
                rtx_ssrc=prev.ssrc if prev is not None else None)
        else:
            self.video_fb.rtx = None

    async def _setup_turn_relay(self, candidates, permission_ips) -> None:
        """Allocate the server-side relayed candidate (shared by both
        signaling directions); appends to ``candidates`` on success."""
        alloc = None
        try:
            from .turn_client import TurnAllocation

            alloc = TurnAllocation(
                (self.turn["host"], int(self.turn["port"])),
                self.turn["username"], self.turn["credential"])
            await asyncio.wait_for(alloc.allocate(), timeout=10.0)
            self.ice.attach_relay(alloc)
            for ip in permission_ips:
                try:
                    await alloc.create_permission(ip)
                except Exception as e:
                    log.warning("TURN permission for %s failed: %s", ip, e)
            rc = self.ice.relay_candidate_line()
            if rc is not None:
                candidates.append(rc)
        except Exception as e:
            log.warning("TURN allocation failed (%s); host candidate "
                        "only", e)
            if alloc is not None:        # close the bound UDP endpoint
                alloc.close()

    async def create_offer(self, with_datachannel: bool = True) -> str:
        """Server-initiated offer (the stock-selkies signaling flow:
        the app's webrtcbin offers sendonly media, the browser answers
        — web/selkies_shim).  Remote credentials arrive later via
        :meth:`handle_answer`.  ``with_datachannel`` negotiates the
        SCTP m=application section the stock client's input rides."""
        self._loop = asyncio.get_running_loop()
        self.ready = self._loop.create_future()
        self.video.pt = sdp.OFFER_VIDEO_PT
        self.audio.pt = sdp.OFFER_AUDIO_PT
        # advertise the full feedback matrix; handle_answer disarms
        # whatever the browser declined
        self.video_fb.nack_enabled = True
        self.video_fb.enable_rtx(sdp.OFFER_VIDEO_RTX_PT)
        await self.ice.bind()
        candidates = [self.ice.candidate_line(self.advertise_ip)]
        if self.turn:
            await self._setup_turn_relay(candidates, ())
        return sdp.build_offer(
            self.ice.local_ufrag, self.ice.local_pwd,
            self.cert.fingerprint, candidates, self.advertise_ip,
            ssrcs={"video": self.video.ssrc, "audio": self.audio.ssrc,
                   "video_rtx": self.video_fb.rtx.ssrc},
            video_codec=self.video_codec, with_audio=self.with_audio,
            with_datachannel=with_datachannel,
            h264_profile=self.h264_profile)

    async def handle_answer(self, answer_sdp: str) -> None:
        """Complete the server-initiated negotiation with the browser's
        answer (credentials + fingerprint; the PTs echo our offer)."""
        answer = sdp.parse_answer(answer_sdp)
        self._offer = answer
        for m in answer.media:
            if m.kind == "application" and m.sctp_port is not None:
                self._sctp_remote_port = m.sctp_port
            elif m.kind == "video":
                self._negotiate_feedback(m)
        self.ice.set_remote_credentials(answer.ice_ufrag, answer.ice_pwd)
        for ip in answer.candidate_ips:
            await self.add_remote_candidate_ip(ip)
        if self._timer_task is None and self._loop is not None:
            self._timer_task = self._loop.create_task(self._dtls_timer())

    async def add_remote_candidate_ip(self, ip: str) -> None:
        """Trickled remote candidate: extend the TURN permission set so
        the relay accepts the new address's checks."""
        alloc = getattr(self.ice, "_relay", None)
        if alloc is not None:
            try:
                await alloc.create_permission(ip)
            except Exception as e:
                log.warning("TURN permission for %s failed: %s", ip, e)

    # -- DTLS / SRTP ---------------------------------------------------

    def _on_dtls(self, data: bytes, addr) -> None:
        if self.srtp_out is not None:
            # post-handshake traffic: control records + the data
            # channel's SCTP packets riding as DTLS application data
            for out in self.dtls.handle_datagram(data):
                self.ice.send(out)
            self._pump_sctp()
            return
        try:
            outs = self.dtls.handle_datagram(data)
        except ConnectionError:
            log.exception("DTLS handshake failed; closing peer")
            self._fail()
            return
        for out in outs:
            self.ice.send(out)
        if self.dtls.handshake_complete:
            self._srtp_up()
            self._pump_sctp()

    def _pump_sctp(self) -> None:
        for pkt in self.dtls.take_app_data():
            if self.sctp is not None:
                self.sctp.receive(pkt)

    def _sctp_transmit(self, packet: bytes) -> None:
        for d in self.dtls.send_app_data(packet):
            self.ice.send(d)

    def _setup_datachannels(self) -> None:
        from .datachannel import DataChannelEndpoint
        from .sctp import SctpAssociation

        # the browser is the DTLS client in both signaling flows (we
        # always end up setup:passive), so it initiates SCTP and opens
        # channels on even stream ids; we answer and own the odd ids
        self.sctp = SctpAssociation(
            role="server", local_port=sdp.SCTP_PORT,
            remote_port=self._sctp_remote_port or sdp.SCTP_PORT,
            on_transmit=self._sctp_transmit)
        if self._pending_sctp is not None:
            # migrated association: seed TSN/SSN past the predecessor's
            # frontier before the handshake advertises the initial TSN
            self.sctp.import_state(self._pending_sctp)
            self._pending_sctp = None
        self.sctp.budget = self.ingress_budget
        self.datachannels = DataChannelEndpoint(
            self.sctp, dtls_role="server",
            on_channel=self._on_channel_open)
        self.datachannels.budget = self.ingress_budget
        if self._loop is not None and self._sctp_task is None:
            self._sctp_task = self._loop.create_task(self._sctp_timer())

    def _on_channel_open(self, channel) -> None:
        if self.on_datachannel is not None:
            try:
                self.on_datachannel(channel)
            except Exception:
                log.exception("on_datachannel callback failed")

    async def _sctp_timer(self) -> None:
        """Retransmission/heartbeat driver for the data channel plane
        (runs for the association's whole life, unlike the DTLS timer
        which retires at handshake completion)."""
        try:
            while not self._closed:
                await asyncio.sleep(0.1)
                if self.sctp is not None:
                    self.sctp.poll_timeout()
                if self.datachannels is not None:
                    self.datachannels.poll()
        except asyncio.CancelledError:
            pass

    def _srtp_up(self) -> None:
        # RFC 8122: the DTLS identity must match the SDP fingerprint
        peer_fp = self.dtls.peer_fingerprint()
        want = (self._offer.fingerprint.split(None, 1)[1].upper()
                if self._offer and " " in self._offer.fingerprint else None)
        if want and peer_fp and peer_fp.upper() != want:
            log.error("DTLS peer fingerprint does not match the offer's "
                      "a=fingerprint (possible MITM); closing peer")
            self._fail()
            return
        lk, ls, rk, rs = self.dtls.export_srtp_keys()
        self.srtp_out = SrtpContext(lk, ls)
        self.srtp_in = SrtpContext(rk, rs)
        if self._pending_srtp_out is not None:
            # migrated peer: fresh session keys (this handshake's), but
            # the predecessor's per-SSRC rollover frontier — a pre-wrap
            # RTX must resolve into its original index era
            self.srtp_out.import_rollover_state(self._pending_srtp_out)
            self._pending_srtp_out = None
        if self._pending_srtp_in is not None:
            self.srtp_in.import_rollover_state(self._pending_srtp_in)
            self._pending_srtp_in = None
        log.info("SRTP up (profile %s)", self.dtls.srtp_profile())
        if self._sctp_remote_port is not None and self.sctp is None:
            self._setup_datachannels()
        if self._rtcp_task is None and self._loop is not None:
            self._rtcp_task = self._loop.create_task(self._rtcp_loop())
        if self._loop is not None:
            # Consent watchdog (RFC 7675): a peer whose checks stop is
            # forgotten (ICE restart) rather than streamed at forever;
            # its revalidation re-fires on_connected -> on_ready below
            # requests a fresh IDR, so resumed media decodes instantly.
            self.ice.on_consent_lost = self._on_consent_lost
            self.ice.start_consent_watch(self._loop)
        if self.ready is not None and not self.ready.done():
            self.ready.set_result(True)
        if self.on_ready is not None:
            try:
                self.on_ready()
            except Exception:
                log.exception("on_ready callback failed")

    def _on_consent_lost(self) -> None:
        """ICE restarted (consent expired): media pauses (ice.send no-ops
        with no validated peer); when the browser's checks revalidate a
        pair, request a fresh IDR so the resumed stream decodes from the
        first frame."""

        def revalidated():
            self.ice.on_connected = None
            if self.on_ready is not None:
                try:
                    self.on_ready()
                except Exception:
                    log.exception("post-restart on_ready failed")

        self.ice.on_connected = revalidated

    def _fail(self) -> None:
        """Handshake/identity failure: resolve ready(False) for anyone
        awaiting it and tear the transport down (no dangling socket)."""
        if self.ready is not None and not self.ready.done():
            self.ready.set_result(False)
        self.close()

    async def _dtls_timer(self) -> None:
        """DTLS retransmission driver until the handshake completes."""
        try:
            while self.srtp_out is None and not self._closed:
                await asyncio.sleep(0.1)
                for out in self.dtls.poll_timeout():
                    self.ice.send(out)
        except asyncio.CancelledError:
            pass

    # -- RTP out -------------------------------------------------------

    @property
    def media_ready(self) -> bool:
        return self.srtp_out is not None and self.ice.remote_addr is not None

    def send_video_au(self, annexb_au: bytes, pts90k: int) -> None:
        """One H.264 access unit (Annex-B) or VP8 frame -> SRTP out.
        Thread-safe: marshals onto the event loop."""
        if not self.media_ready or self._loop is None:
            return
        self._loop.call_soon_threadsafe(self._send_video, annexb_au,
                                        pts90k)

    def _transmit_video(self, pkt: bytes) -> None:
        """Plain RTP out of the feedback plane/pacer -> SRTP -> wire.
        Packets released after a teardown or before SRTP are dropped
        (the pacer's close() flush can race the DTLS teardown).  The
        sent-packet/byte counters live HERE — actual wire egress —
        so pacer-dropped packets are not counted and RTX
        retransmissions are (offered-vs-sent divergence under
        overload is exactly what these counters must show)."""
        if self.srtp_out is None:
            return
        self.ice.send(self.srtp_out.protect(pkt))
        self._m_vpkts.inc()
        self._m_vbytes.inc(len(pkt))

    def _send_video(self, au: bytes, pts90k: int) -> None:
        if not self.media_ready:
            return
        t0 = time.perf_counter()
        if self.video_codec == "H264":
            payloads = rtp.packetize_h264(split_annexb(au))
        else:
            payloads = rtp.packetize_vp8(au)
        # history + pacer + transmit (webrtc/feedback): every packet is
        # remembered for NACK repair, bursts drain on the pacer budget
        # (egress metrics count in _transmit_video, where the wire is)
        npkt, _ = self.video_fb.send_frame(payloads, pts90k)
        # rtp-sent span closes the per-frame pipeline trace: the AU's
        # pts (passed through from the encode thread verbatim) is the
        # key the 'pipeline' track tags its spans with
        self._tracer.record_span("rtp-sent", t0,
                                 time.perf_counter() - t0,
                                 pts=pts90k)
        if self.journeys is not None and npkt:
            # absolute index of this frame's LAST packet (1-based):
            # packet_count only ever grows, so the RR mapping below is
            # wrap-free on our side
            self._frame_log.note_frame(self.video.packet_count, pts90k)

    # -- inbound feedback (rtcp.PeerRtcpMonitor hooks) -----------------

    def _on_nack(self, kind: str, seqs) -> None:
        if kind == "video":
            self.video_fb.on_nack(seqs)

    def _on_pli(self, kind: str, source: str) -> None:
        if kind == "video":
            self.video_fb.on_pli(source)

    def _on_remb(self, bitrate_bps: float, ssrcs) -> None:
        self.video_fb.on_remb(bitrate_bps, ssrcs)

    def _keyframe_requested(self, reason: str) -> None:
        """PLI/FIR landed: route into the session's rate-limited
        ``request_idr`` (shared with the degrade ladder's IDR rung and
        the collect-failure resync, so a PLI storm costs one IDR)."""
        cb = self.on_keyframe_request
        if cb is None:
            return
        try:
            cb(reason)
        except Exception:
            log.exception("keyframe request callback failed")

    def _on_rr_block(self, kind: str, blk: dict,
                     rtt_ms: Optional[float]) -> None:
        """RTCP-fallback journey closure at ``now - rtt/2`` (the RR's
        flight time back to us; receipt happened roughly half an RTT
        ago — plus up to one RR interval of staleness, so the rtcp
        method is a conservative UPPER bound like the ack method).

        Honesty under loss: the extended-highest-seq advances past
        dropped packets, so it only proves full delivery when the
        report interval was loss-free.  A block reporting
        ``fraction_lost > 0`` retires the covered frames WITHOUT
        closing them — they age out as ``dngd_journey_expired_total``
        instead of feeding dngd_g2g_* as successful deliveries.  (A
        NACK-repaired frame is complete at the receiver, but the RR
        cannot tell us WHICH holes were filled — staying conservative
        keeps the g2g numbers loss-honest across retransmits.)

        The seq mapping is 16-bit-wrap-safe: the report's extended
        highest is resolved against our own send frontier
        (feedback.FrameSeqLog), so receivers that lose their cycle
        count no longer silently stop closing journeys at the first
        2^16 wrap."""
        if kind != "video" or self.journeys is None:
            return
        lossy = blk.get("fraction_lost", 0) > 0
        t = time.perf_counter() - (rtt_ms / 2e3 if rtt_ms else 0.0)
        for pts in self._frame_log.pop_covered(blk["highest_seq"],
                                               self.video.packet_count):
            if lossy:
                continue                 # possibly-incomplete frame
            try:
                self.journeys.close_by_pts(pts, t, method="rtcp")
            except Exception:
                log.exception("rtcp journey closure failed")

    def send_audio(self, opus_packet: bytes, pts90k: int) -> None:
        if not self.media_ready or self._loop is None:
            return
        self._loop.call_soon_threadsafe(self._send_audio, opus_packet,
                                        pts90k)

    def _unwrap90k(self, pts: int) -> int:
        """32-bit 90 kHz clock -> monotonically increasing 64-bit."""
        if self._pts_last is None:
            self._pts_last = pts
            self._pts_acc = pts
            return self._pts_acc
        delta = (pts - self._pts_last) & 0xFFFFFFFF
        if delta >= 1 << 31:
            delta -= 1 << 32
        self._pts_acc += delta
        self._pts_last = pts
        return self._pts_acc

    def _ts48(self, pts90k: int) -> int:
        """Audio RTP timestamp: rescale the UNWRAPPED clock so the 2^32
        wrap of the 90 kHz clock stays a clean RTP wrap at 48 kHz."""
        return ((self._unwrap90k(pts90k) * 8) // 15) & 0xFFFFFFFF

    def _send_audio(self, packet: bytes, pts90k: int) -> None:
        if not self.media_ready:
            return
        pkt = self.audio.packet(packet, self._ts48(pts90k), marker=False)
        self.ice.send(self.srtp_out.protect(pkt))
        self._m_apkts.inc()
        self._m_abytes.inc(len(pkt))

    # -- RTCP ----------------------------------------------------------

    async def _rtcp_loop(self) -> None:
        try:
            while not self._closed:
                await asyncio.sleep(self.RTCP_INTERVAL_S)
                if not self.media_ready:
                    continue
                now = self.clock.now90k()
                for stream, ts in ((self.video, now),
                                   (self.audio, self._ts48(now))):
                    if stream.packet_count == 0:
                        continue
                    sr = rtcp.compound_sr(stream.ssrc, ts,
                                          stream.packet_count,
                                          stream.octet_count)
                    self.ice.send(self.srtp_out.protect_rtcp(sr))
        except asyncio.CancelledError:
            pass

    def _on_rtp(self, data: bytes, addr) -> None:
        # sendonly: inbound is the browser's SRTCP — RRs are the only
        # live view of the wire (RTT / jitter / loss); feed the gauges.
        # RFC 5761 demux: RTCP packet types occupy 192..223 in byte 1.
        if (self.srtp_in is None or len(data) < 8
                or not 192 <= data[1] <= 223):
            return
        try:
            plain = self.srtp_in.unprotect_rtcp(data)
        except Exception:
            return                       # replay/garbage: not a peer error
        try:
            self.rtcp_monitor.ingest(plain)
        except Exception:
            log.exception("RTCP RR ingestion failed")

    # -- teardown ------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        _M_PEERS.dec()
        for hook in self.close_hooks:
            try:
                hook()
            except Exception:
                log.exception("peer close hook failed")
        self.close_hooks.clear()
        self.rtcp_monitor.close()        # retire per-peer SSRC series
        self.pacer.close()               # flush queued media unpaced
        self.video_fb.close()            # retire per-peer REMB series
        for task in (self._rtcp_task, self._timer_task, self._sctp_task):
            if task is not None:
                task.cancel()
        if self.datachannels is not None:
            self.datachannels.close()
        if self.sctp is not None:
            self.sctp.close()
        self.ice.close()
        self.dtls.close()

    # -- handoff continuity (resilience/handoff) -----------------------

    def export_wire(self) -> dict:
        """The continuity set a successor peer needs so the SAME client
        resumes the SAME streams: SSRC + seq frontier per RTP stream,
        per-SSRC SRTP rollover geometry, SCTP TSN/SSN counters."""
        wire = {"video": self.video.export_state(),
                "audio": self.audio.export_state()}
        if self.srtp_out is not None:
            wire["srtp_out"] = self.srtp_out.export_rollover_state()
        if self.srtp_in is not None:
            wire["srtp_in"] = self.srtp_in.export_rollover_state()
        if self.sctp is not None:
            wire["sctp"] = self.sctp.export_state()
        return wire

    def import_wire(self, wire: dict) -> None:
        """Adopt a predecessor's wire state.  Must run BEFORE
        :meth:`handle_offer` (the SDP advertises the imported SSRCs);
        SRTP rollover and SCTP seeds park until the objects they apply
        to exist (post-DTLS / post-offer)."""
        if wire.get("video"):
            self.video.import_state(wire["video"])
        if wire.get("audio"):
            self.audio.import_state(wire["audio"])
        self._pending_srtp_out = wire.get("srtp_out")
        self._pending_srtp_in = wire.get("srtp_in")
        self._pending_sctp = wire.get("sctp")
        # everything keyed on SSRC at construction re-keys to the
        # imported identities: RR attribution + journey closure ...
        cbs = (self.rtcp_monitor.on_block, self.rtcp_monitor.on_nack,
               self.rtcp_monitor.on_pli, self.rtcp_monitor.on_remb)
        budget = self.rtcp_monitor.budget
        self.rtcp_monitor.close()
        self.rtcp_monitor = rtcp.PeerRtcpMonitor({
            self.video.ssrc: ("video", 90_000),
            self.audio.ssrc: ("audio", 48_000)})
        (self.rtcp_monitor.on_block, self.rtcp_monitor.on_nack,
         self.rtcp_monitor.on_pli, self.rtcp_monitor.on_remb) = cbs
        self.rtcp_monitor.budget = budget
        # ... and the frame->seq journey log restarts at the imported
        # send frontier so the first post-migration RR closes honestly
        self._frame_log = feedback.FrameSeqLog(self.video.seq)

    def stats(self) -> dict:
        return {
            "media_ready": self.media_ready,
            "video": {"ssrc": self.video.ssrc, "pt": self.video.pt,
                      "packets": self.video.packet_count,
                      "octets": self.video.octet_count},
            "audio": {"ssrc": self.audio.ssrc, "pt": self.audio.pt,
                      "packets": self.audio.packet_count,
                      "octets": self.audio.octet_count},
            # latest browser-side wire quality (RTCP RRs)
            "remote": self.rtcp_monitor.summary(),
            # loss recovery (NACK/RTX history, pacer, REMB headroom)
            "feedback": self.video_fb.stats(),
            "datachannel": {
                "negotiated": self._sctp_remote_port is not None,
                "sctp": (self.sctp.stats()
                         if self.sctp is not None else None),
                "channels": ([{"label": c.label, "stream": c.stream_id,
                               "state": c.state}
                              for c in
                              self.datachannels.channels.values()]
                             if self.datachannels is not None else []),
            },
        }
