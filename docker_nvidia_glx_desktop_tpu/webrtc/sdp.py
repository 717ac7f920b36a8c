"""SDP offer/answer for the browser's RTCPeerConnection (RFC 8829 subset).

The browser offers recvonly video+audio transceivers (the web client
drives this); the answer advertises our sendonly tracks, ICE-lite
credentials, the DTLS fingerprint (setup:passive — we are the DTLS
server), rtcp-mux, BUNDLE, and one host candidate.  An
``m=application .. webrtc-datachannel`` section (RFC 8841) negotiates
the SCTP data channel that carries the stock selkies client's
input/clipboard/stats — both the browser-offers flow and the
role-inverted server offer (``build_offer``) include it, so input rides
the same DTLS association as media (``webrtc/sctp.py``); the first-party
client keeps the WebSocket input path as fallback.
"""

from __future__ import annotations

import dataclasses
import secrets
from typing import Dict, List, Optional

__all__ = ["RemoteOffer", "SdpError", "parse_offer", "build_answer",
           "build_offer", "parse_answer", "SCTP_PORT",
           "MAX_MESSAGE_SIZE", "SUPPORTED_VIDEO_FB",
           "OFFER_VIDEO_RTX_PT"]

# Hard bounds on what we will even scan (resilience/ingress trust
# boundary): a real browser offer is a few KiB with < 100 lines and at
# most a handful of m-sections; anything past these caps is hostile or
# corrupt, and rejecting early keeps the parser O(small) regardless of
# what arrives on the signaling socket.
MAX_SDP_BYTES = 64 * 1024
MAX_SDP_LINES = 512
MAX_SDP_LINE_LEN = 1024
MAX_MEDIA_SECTIONS = 8


class SdpError(ValueError):
    """Offer/answer rejected at the trust boundary.  Subclasses
    ValueError so pre-hardening callers that caught ValueError still
    do; ``reason`` is the violation label the signaling handlers feed
    to ``PeerBudget.violation`` (dngd_ingress_violations_total)."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(detail or reason)
        self.reason = reason

# Fixed payload types for server-initiated offers (the selkies flow:
# the app's webrtcbin offers, the browser answers — selkies-gstreamer
# signalling; the numbers themselves are arbitrary dynamic PTs)
OFFER_VIDEO_PT = 102
OFFER_AUDIO_PT = 111

# SCTP-over-DTLS port we advertise (a=sctp-port; the value is opaque —
# both stacks demux on the DTLS association, 5000 is the WebRTC norm)
SCTP_PORT = 5000
MAX_MESSAGE_SIZE = 262144

# RTX payload type for server-initiated offers (RFC 4588; apt= maps it
# back to OFFER_VIDEO_PT)
OFFER_VIDEO_RTX_PT = 103

# The RTCP feedback mechanisms we actually implement (webrtc/rtcp +
# webrtc/feedback); the answer echoes only the intersection with what
# the browser offered, so a stock client never sees a capability we
# would ignore.
SUPPORTED_VIDEO_FB = ("nack", "nack pli", "ccm fir", "goog-remb")


@dataclasses.dataclass
class MediaSection:
    kind: str                     # "video" | "audio" | "application"
    mid: str
    payload_type: Optional[int]   # chosen codec PT (None = unsupported)
    codec: str = ""               # "H264" | "VP8" | "opus"
    fmtp: str = ""                # echoed back for H264
    # RTCP feedback the peer offered for the chosen PT (a=rtcp-fb
    # lines, "*" wildcard included): "nack", "nack pli", "ccm fir",
    # "goog-remb", ... — the answer echoes the supported subset
    feedback: tuple = ()
    # RFC 4588 retransmission PT whose a=fmtp apt= names the chosen PT
    rtx_payload_type: Optional[int] = None
    # application (data channel) sections: the peer's SCTP-over-DTLS
    # port (None = not a webrtc-datachannel section) + negotiated limits
    sctp_port: Optional[int] = None
    max_message_size: int = 0
    proto: str = ""               # m-line proto, echoed in the answer


@dataclasses.dataclass
class RemoteOffer:
    ice_ufrag: str
    ice_pwd: str
    fingerprint: str              # "sha-256 AB:CD:..."
    media: List[MediaSection] = dataclasses.field(default_factory=list)
    # connection addresses from the offer's a=candidate lines — the TURN
    # relay path installs permissions for these (RFC 5766 §9)
    candidate_ips: List[str] = dataclasses.field(default_factory=list)


def _codec_table(lines: List[str]) -> Dict[int, dict]:
    """payload type -> {codec, clock, fmtp} from one m-section."""
    table: Dict[int, dict] = {}
    for ln in lines:
        if ln.startswith("a=rtpmap:"):
            body = ln[len("a=rtpmap:"):]
            pt_s, _, enc = body.partition(" ")
            name = enc.split("/")[0]
            try:
                table.setdefault(int(pt_s), {})["codec"] = name
            except ValueError:
                pass
    for ln in lines:
        if ln.startswith("a=fmtp:"):
            body = ln[len("a=fmtp:"):]
            pt_s, _, params = body.partition(" ")
            try:
                pt = int(pt_s)
            except ValueError:
                continue
            if pt in table:
                table[pt]["fmtp"] = params
    return table


def _feedback_table(lines: List[str]) -> Dict[object, List[str]]:
    """``a=rtcp-fb:<pt|*> <mech...>`` lines of one m-section: payload
    type (or the ``"*"`` wildcard, RFC 4585 §4.2) -> feedback list."""
    table: Dict[object, List[str]] = {}
    for ln in lines:
        if not ln.startswith("a=rtcp-fb:"):
            continue
        body = ln[len("a=rtcp-fb:"):]
        pt_s, _, mech = body.partition(" ")
        mech = mech.strip()
        if not mech:
            continue
        key: object
        if pt_s == "*":
            key = "*"
        else:
            try:
                key = int(pt_s)
            except ValueError:
                continue
        table.setdefault(key, []).append(mech)
    return table


def _feedback_for(table: Dict[object, List[str]], pt: int) -> tuple:
    fb = list(table.get("*", ())) + list(table.get(pt, ()))
    seen, out = set(), []
    for m in fb:
        if m not in seen:
            seen.add(m)
            out.append(m)
    return tuple(out)


def _rtx_for(codec_table: Dict[int, dict], pt: int) -> Optional[int]:
    """The RTX payload type whose ``apt=`` names ``pt`` (RFC 4588)."""
    for cand_pt, info in codec_table.items():
        if info.get("codec", "").lower() != "rtx":
            continue
        for param in info.get("fmtp", "").split(";"):
            k, _, v = param.strip().partition("=")
            if k == "apt" and v.strip() == str(pt):
                return cand_pt
    return None


H264_BASELINE, H264_MAIN = "42e01f", "4d001f"


def h264_profile_level_id(sps: Optional[bytes]) -> str:
    """What our H.264 stream is negotiated as, from its SPS NAL: Main
    (``4d001f``) when ``profile_idc`` is 77 (ENCODER_ENTROPY=cabac),
    else constrained baseline (``42e01f``).  Level 3.1 in both, as
    browsers offer it: the decoder goes by the SPS in the stream."""
    return H264_MAIN if sps and len(sps) > 1 and sps[1] == 77 \
        else H264_BASELINE


def _choose_video_pt(table: Dict[int, dict], prefer: str,
                     h264_profile: str = H264_BASELINE):
    """Pick our codec's payload type from the browser's offer."""
    if prefer == "H264":
        # packetization-mode=1 + the profile of our stream: constrained
        # baseline 42xx is what the slice-per-row CAVLC encoder emits,
        # Main 4dxx the CABAC one
        for pt, info in table.items():
            if info.get("codec") != "H264":
                continue
            fmtp = info.get("fmtp", "")
            if ("packetization-mode=1" in fmtp
                    and f"profile-level-id={h264_profile[:2]}" in fmtp):
                return pt, info
        for pt, info in table.items():      # any packetization-mode=1 H264
            if (info.get("codec") == "H264"
                    and "packetization-mode=1" in info.get("fmtp", "")):
                return pt, info
    for pt, info in table.items():
        if info.get("codec") == prefer:
            return pt, info
    return None, {}


def parse_offer(sdp: str, video_codec: str = "H264",
                h264_profile: str = H264_BASELINE) -> RemoteOffer:
    if not isinstance(sdp, str):
        raise SdpError("sdp_not_text")
    if len(sdp) > MAX_SDP_BYTES:
        raise SdpError("sdp_oversized",
                       f"offer is {len(sdp)} bytes (cap {MAX_SDP_BYTES})")
    lines = [ln.strip() for ln in sdp.replace("\r\n", "\n").split("\n")]
    if len(lines) > MAX_SDP_LINES:
        raise SdpError("sdp_oversized",
                       f"offer has {len(lines)} lines (cap {MAX_SDP_LINES})")
    if any(len(ln) > MAX_SDP_LINE_LEN for ln in lines):
        raise SdpError("sdp_oversized",
                       f"offer line exceeds {MAX_SDP_LINE_LEN} chars")
    ufrag = pwd = fp = ""
    media: List[MediaSection] = []
    sections: List[List[str]] = [[]]
    for ln in lines:
        if ln.startswith("m="):
            sections.append([ln])
        else:
            sections[-1].append(ln)
    if len(sections) - 1 > MAX_MEDIA_SECTIONS:
        raise SdpError("sdp_oversized",
                       f"offer has {len(sections) - 1} media sections "
                       f"(cap {MAX_MEDIA_SECTIONS})")
    # session-level credentials apply to every m-section unless overridden
    for ln in sections[0]:
        if ln.startswith("a=ice-ufrag:"):
            ufrag = ln.split(":", 1)[1]
        elif ln.startswith("a=ice-pwd:"):
            pwd = ln.split(":", 1)[1]
        elif ln.startswith("a=fingerprint:"):
            fp = ln.split(":", 1)[1]
    for sec in sections[1:]:
        mline = sec[0]
        mparts = mline.split()
        kind = mparts[0][2:]
        proto = mparts[2] if len(mparts) > 2 else ""
        mid = ""
        sctp_port: Optional[int] = None
        max_msg = 0
        for ln in sec:
            if ln.startswith("a=mid:"):
                mid = ln.split(":", 1)[1]
            elif ln.startswith("a=ice-ufrag:"):
                ufrag = ln.split(":", 1)[1]
            elif ln.startswith("a=ice-pwd:"):
                pwd = ln.split(":", 1)[1]
            elif ln.startswith("a=fingerprint:"):
                fp = ln.split(":", 1)[1]
            elif ln.startswith("a=sctp-port:"):
                try:
                    sctp_port = int(ln.split(":", 1)[1])
                except ValueError:
                    pass
            elif ln.startswith("a=sctpmap:"):
                # legacy datachannel style: a=sctpmap:5000 webrtc-...
                try:
                    sctp_port = int(ln.split(":", 1)[1].split()[0])
                except (ValueError, IndexError):
                    pass
            elif ln.startswith("a=max-message-size:"):
                try:
                    max_msg = int(ln.split(":", 1)[1])
                except ValueError:
                    pass
        table = _codec_table(sec)
        if kind == "application" and "SCTP" in proto.upper():
            if sctp_port is None:
                # new-style m-lines put nothing useful past the proto;
                # legacy ones carry the port as the fmt token
                try:
                    sctp_port = int(mparts[3])
                except (ValueError, IndexError):
                    sctp_port = SCTP_PORT
            if not 0 < sctp_port <= 0xFFFF:
                # a lying a=sctpmap/a=sctp-port value would make the
                # SCTP header pack raise long after signaling; clamp to
                # the convention port instead
                sctp_port = SCTP_PORT
            media.append(MediaSection(kind, mid, None,
                                      sctp_port=sctp_port,
                                      max_message_size=max_msg,
                                      proto=proto))
        elif kind == "video":
            pt, info = _choose_video_pt(table, video_codec,
                                        h264_profile)
            fb_table = _feedback_table(sec)
            media.append(MediaSection(
                kind, mid, pt, info.get("codec", ""),
                info.get("fmtp", ""),
                feedback=(_feedback_for(fb_table, pt)
                          if pt is not None else ()),
                rtx_payload_type=(_rtx_for(table, pt)
                                  if pt is not None else None)))
        elif kind == "audio":
            pt, info = None, {}
            for cand_pt, cand in table.items():
                if cand.get("codec", "").lower() == "opus":
                    pt, info = cand_pt, cand
                    break
            media.append(MediaSection(kind, mid, pt, "opus",
                                      info.get("fmtp", "")))
        else:
            media.append(MediaSection(kind, mid, None))
    if not ufrag or not pwd or not fp:
        raise SdpError("sdp_no_credentials",
                       "offer lacks ice credentials or fingerprint")
    cand_ips: List[str] = []
    for ln in lines:
        if ln.startswith("a=candidate:"):
            parts = ln.split()
            if len(parts) >= 5 and parts[4] not in cand_ips:
                cand_ips.append(parts[4])
    return RemoteOffer(ufrag, pwd, fp, media, cand_ips)


def _append_application_section(out: List[str], proto: str, mid: str,
                                advertise_ip: str, ice_ufrag: str,
                                ice_pwd: str, fingerprint: str,
                                setup: str, candidates) -> None:
    """One ``m=application`` (data channel) section, RFC 8841 style —
    or the legacy ``DTLS/SCTP`` + ``a=sctpmap`` shape when that is what
    the peer offered."""
    legacy = "sctpmap" in proto.lower() or proto.upper() == "DTLS/SCTP"
    fmt = str(SCTP_PORT) if legacy else "webrtc-datachannel"
    out.append(f"m=application 9 {proto} {fmt}")
    out.append(f"c=IN IP4 {advertise_ip}")
    out.append(f"a=mid:{mid}")
    out += [
        f"a=ice-ufrag:{ice_ufrag}",
        f"a=ice-pwd:{ice_pwd}",
        f"a=fingerprint:sha-256 {fingerprint}",
        f"a=setup:{setup}",
    ]
    if legacy:
        out.append(f"a=sctpmap:{SCTP_PORT} webrtc-datachannel 65535")
    else:
        out.append(f"a=sctp-port:{SCTP_PORT}")
    out.append(f"a=max-message-size:{MAX_MESSAGE_SIZE}")
    for cand in candidates:
        out.append(f"a={cand}")
    out.append("a=end-of-candidates")


def build_answer(offer: RemoteOffer, ice_ufrag: str, ice_pwd: str,
                 fingerprint: str, candidate, advertise_ip: str,
                 ssrcs: Dict[str, int],
                 video_codec: str = "H264",
                 h264_profile: str = H264_BASELINE) -> str:
    """Answer SDP: ICE-lite, sendonly media, BUNDLE, rtcp-mux.

    ``candidate``: one ``candidate:...`` line or a list of them (host
    first, then relay when a TURN allocation exists)."""
    candidates = ([candidate] if isinstance(candidate, str)
                  else list(candidate))
    sess = secrets.randbits(62)
    mids = " ".join(m.mid for m in offer.media)
    out = [
        "v=0",
        f"o=- {sess} 2 IN IP4 127.0.0.1",
        "s=-",
        "t=0 0",
        "a=ice-lite",
        f"a=group:BUNDLE {mids}",
        "a=msid-semantic: WMS tpu-desktop",
    ]
    for m in offer.media:
        if m.kind == "application" and m.sctp_port is not None:
            _append_application_section(
                out, m.proto or "UDP/DTLS/SCTP", m.mid, advertise_ip,
                ice_ufrag, ice_pwd, fingerprint, "passive", candidates)
            continue
        port = "9" if m.payload_type is not None else "0"
        pt = m.payload_type if m.payload_type is not None else 0
        proto = "UDP/TLS/RTP/SAVPF"
        # RTX (RFC 4588) goes out only when the browser offered BOTH
        # nack feedback and an apt-mapped rtx PT for the chosen codec,
        # and the caller minted an RTX SSRC to pair with it
        fb = [f for f in SUPPORTED_VIDEO_FB if f in m.feedback] \
            if m.kind == "video" else []
        rtx_ssrc = ssrcs.get("video_rtx")
        rtx_pt = (m.rtx_payload_type
                  if (m.kind == "video" and "nack" in fb
                      and rtx_ssrc is not None) else None)
        fmt_list = f"{pt} {rtx_pt}" if rtx_pt is not None else str(pt)
        out.append(f"m={m.kind} {port} {proto} {fmt_list}")
        out.append(f"c=IN IP4 {advertise_ip}")
        out.append("a=rtcp:9 IN IP4 0.0.0.0")
        out.append(f"a=mid:{m.mid}")
        if m.payload_type is None:
            out.append("a=inactive")
            continue
        out += [
            f"a=ice-ufrag:{ice_ufrag}",
            f"a=ice-pwd:{ice_pwd}",
            f"a=fingerprint:sha-256 {fingerprint}",
            "a=setup:passive",
            "a=sendonly",
            "a=rtcp-mux",
            f"a=msid:tpu-desktop tpu-{m.kind}",
        ]
        if m.kind == "video":
            if m.codec == "H264":
                out.append(f"a=rtpmap:{pt} H264/90000")
                fmtp = m.fmtp or ("level-asymmetry-allowed=1;"
                                  "packetization-mode=1;"
                                  f"profile-level-id={h264_profile}")
                out.append(f"a=fmtp:{pt} {fmtp}")
            else:
                out.append(f"a=rtpmap:{pt} VP8/90000")
            for f in fb:
                out.append(f"a=rtcp-fb:{pt} {f}")
            if rtx_pt is not None:
                out.append(f"a=rtpmap:{rtx_pt} rtx/90000")
                out.append(f"a=fmtp:{rtx_pt} apt={pt}")
        else:
            out.append(f"a=rtpmap:{pt} opus/48000/2")
            out.append(f"a=fmtp:{pt} minptime=10;useinbandfec=1")
        ssrc = ssrcs.get(m.kind, 0)
        if rtx_pt is not None:
            out.append(f"a=ssrc-group:FID {ssrc} {rtx_ssrc}")
        out.append(f"a=ssrc:{ssrc} cname:tpu-desktop")
        out.append(f"a=ssrc:{ssrc} msid:tpu-desktop tpu-{m.kind}")
        if rtx_pt is not None:
            out.append(f"a=ssrc:{rtx_ssrc} cname:tpu-desktop")
            out.append(f"a=ssrc:{rtx_ssrc} msid:tpu-desktop "
                       f"tpu-{m.kind}")
        for cand in candidates:
            out.append(f"a={cand}")
        out.append("a=end-of-candidates")
    return "\r\n".join(out) + "\r\n"


def build_offer(ice_ufrag: str, ice_pwd: str, fingerprint: str,
                candidate, advertise_ip: str, ssrcs: Dict[str, int],
                video_codec: str = "H264",
                with_audio: bool = True,
                with_datachannel: bool = True,
                h264_profile: str = H264_BASELINE) -> str:
    """Server-initiated offer (the stock-selkies role inversion: the
    app offers sendonly media, the browser answers).  ICE-lite with
    setup:actpass — the full-ICE browser takes the controlling role and
    answers setup:active, leaving us the DTLS server exactly as in the
    browser-offers flow.  ``with_datachannel`` appends the
    ``m=application webrtc-datachannel`` section the stock selkies app
    binds its input/clipboard/stats channels to."""
    candidates = ([candidate] if isinstance(candidate, str)
                  else list(candidate))
    sess = secrets.randbits(62)
    sections = [("video", "0", OFFER_VIDEO_PT)]
    if with_audio:
        sections.append(("audio", "1", OFFER_AUDIO_PT))
    mids = [mid for _, mid, _ in sections]
    app_mid = None
    if with_datachannel:
        app_mid = str(len(sections))
        mids.append(app_mid)
    out = [
        "v=0",
        f"o=- {sess} 2 IN IP4 127.0.0.1",
        "s=-",
        "t=0 0",
        "a=ice-lite",
        "a=group:BUNDLE " + " ".join(mids),
        "a=msid-semantic: WMS tpu-desktop",
    ]
    for kind, mid, pt in sections:
        rtx_ssrc = ssrcs.get("video_rtx")
        rtx_pt = (OFFER_VIDEO_RTX_PT
                  if kind == "video" and rtx_ssrc is not None else None)
        fmt_list = f"{pt} {rtx_pt}" if rtx_pt is not None else str(pt)
        out.append(f"m={kind} 9 UDP/TLS/RTP/SAVPF {fmt_list}")
        out.append(f"c=IN IP4 {advertise_ip}")
        out.append("a=rtcp:9 IN IP4 0.0.0.0")
        out.append(f"a=mid:{mid}")
        out += [
            f"a=ice-ufrag:{ice_ufrag}",
            f"a=ice-pwd:{ice_pwd}",
            f"a=fingerprint:sha-256 {fingerprint}",
            "a=setup:actpass",
            "a=sendonly",
            "a=rtcp-mux",
            f"a=msid:tpu-desktop tpu-{kind}",
        ]
        if kind == "video":
            if video_codec == "H264":
                out.append(f"a=rtpmap:{pt} H264/90000")
                out.append(f"a=fmtp:{pt} level-asymmetry-allowed=1;"
                           "packetization-mode=1;"
                           f"profile-level-id={h264_profile}")
            else:
                out.append(f"a=rtpmap:{pt} VP8/90000")
            for f in SUPPORTED_VIDEO_FB:
                out.append(f"a=rtcp-fb:{pt} {f}")
            if rtx_pt is not None:
                out.append(f"a=rtpmap:{rtx_pt} rtx/90000")
                out.append(f"a=fmtp:{rtx_pt} apt={pt}")
        else:
            out.append(f"a=rtpmap:{pt} opus/48000/2")
            out.append(f"a=fmtp:{pt} minptime=10;useinbandfec=1")
        ssrc = ssrcs.get(kind, 0)
        if rtx_pt is not None:
            out.append(f"a=ssrc-group:FID {ssrc} {rtx_ssrc}")
        out.append(f"a=ssrc:{ssrc} cname:tpu-desktop")
        out.append(f"a=ssrc:{ssrc} msid:tpu-desktop tpu-{kind}")
        if rtx_pt is not None:
            out.append(f"a=ssrc:{rtx_ssrc} cname:tpu-desktop")
            out.append(f"a=ssrc:{rtx_ssrc} msid:tpu-desktop tpu-{kind}")
        for cand in candidates:
            out.append(f"a={cand}")
        out.append("a=end-of-candidates")
    if app_mid is not None:
        _append_application_section(
            out, "UDP/DTLS/SCTP", app_mid, advertise_ip, ice_ufrag,
            ice_pwd, fingerprint, "actpass", candidates)
    return "\r\n".join(out) + "\r\n"


def parse_answer(sdp: str) -> RemoteOffer:
    """Browser answer to :func:`build_offer` — same surface as
    :func:`parse_offer` (credentials, fingerprint, candidate IPs); the
    payload types are the ones we offered, echoed back."""
    return parse_offer(sdp)
