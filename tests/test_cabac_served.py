"""The served per-frame CABAC path (ENCODER_ENTROPY=cabac through
``make_encoder``, the deployment ``benchmark/configs/desk1080-cabac.json``) at
128x96: against the plain references (the pure-Python CABAC coder on the same
level tensors; cv2's ffmpeg as the independent decoder), with ``qp`` traced,
the pull ladder warmed, its stages sampled and its fallbacks counted.  And
what ``desk2160-cabac`` adds: the level a stream declares, and the same
comparison at 3840x32, the 4K deployment's 240 macroblocks a row (slow tier:
40 s of XLA:CPU compiles)."""

import json
import pathlib
import sys
import threading

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.utils.config import from_env

cv2 = pytest.importorskip("cv2")

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# the Python reference coder on a submitted frame's level tensors: the
# by-hand check of the timed size uses the same (PERF.md PR 28)
from benchmark.cabac_reference import reference_unit  # noqa: E402

W, H = 128, 96
NR, NC = H // 16, W // 16
CONFIG_ENV = json.loads(
    (ROOT / "benchmark" / "configs" / "desk1080-cabac.json").read_text())["env"]


def config(w=W, h=H, **over):
    """The deployment's environment, geometry overridden."""
    return from_env(dict(CONFIG_ENV, SIZEW=str(w), SIZEH=str(h),
                         PASSWD="pw", **over))


def frame(c: int, noise: float = 10.0, seed: int = 28, w=W, h=H) -> np.ndarray:
    """Seeded noise over ramps that pan by (2c, c): the search finds the
    ramps, the noise leaves levels in every macroblock."""
    yy, xx = np.mgrid[c:c + h, 2 * c:2 * c + w]
    v = (xx * 1.5 + yy * 0.7) % 256 + np.random.default_rng(
        seed + c).normal(0.0, noise, (h, w))
    return np.clip(np.stack([v, 0.8 * v + 20, 255 - v], axis=-1),
                   0, 255).astype(np.uint8)


def counter(name: str) -> float:
    m = obsm.REGISTRY.get(name)
    return sum(child.value for _, child in m.series())


def samples(stage: str) -> int:
    return obsm.REGISTRY.get(f"dngd_stage_{stage}_ms")._default.count


def decode_luma(data: bytes, path, w=W, h=H):
    """The decoder's own luma planes (no colour conversion of cv2's)."""
    path.write_bytes(data)
    cap = cv2.VideoCapture(str(path))
    cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
    out = []
    while True:
        ok, img = cap.read()
        if not ok:
            break
        out.append(np.asarray(img).reshape(-1)[:w * h].reshape(h, w).copy())
    cap.release()
    return out


@pytest.fixture(scope="module", autouse=True)
def small_buckets():
    """Rungs of 4 KiB, so that a 128x96 record buffer (184 KiB) has the
    ladder a 1080p one has with 64 KiB rungs."""
    from docker_nvidia_glx_desktop_tpu.models.prefix_pull import PrefixPull

    mp = pytest.MonkeyPatch()
    mp.setattr(PrefixPull, "BUCKET", 1 << 10)
    yield
    mp.undo()


def served_encoder(w=W, h=H, **over):
    from docker_nvidia_glx_desktop_tpu.models import make_encoder

    enc, name = make_encoder(config(w, h, **over), w, h)
    assert name == "h264_cabac" and enc._dyn_qp
    return enc


@pytest.fixture(scope="module")
def encoder():
    """The served encoder as codec set-up leaves it: pull ladder warmed."""
    enc = served_encoder()
    assert enc.cabac_device_binarize and enc._cabac_native
    # every rung up to the whole buffer, for both kinds of frame
    assert enc.warm_pulls() >= 2 * 10
    return enc


@pytest.mark.parametrize("w,h,n", [
    (W, H, 6), pytest.param(3840, 32, 4, marks=pytest.mark.slow)])
def test_served_stream_is_the_reference_coders_and_the_decoders(
        w, h, n, request, tmp_path):
    """1 IDR + 5 P frames with the rate controller moving ``qp`` (at
    3840x32, a 4K picture's 240 macroblocks a row, 1 + 3): every access
    unit is byte for byte what the Python reference coder makes of the
    same levels, and the independent decoder's luma is the encoder's own
    reference picture after every frame."""
    enc = (request.getfixturevalue("encoder") if (w, h) == (W, H)
           else served_encoder(w, h))
    enc.request_keyframe()
    data, refs, qps, keys = enc.headers(), [], [], []
    for c in range(n):
        token = enc.encode_submit(frame(c, w=w, h=h))
        want = reference_unit(enc, token)
        ef = enc.encode_collect(token)
        assert ef.data == want, f"frame {c} differs from the reference coder"
        data += ef.data
        keys.append(ef.keyframe)
        qps.append(token[4][-2])
        refs.append(np.array(enc.export_state()["ref"][0][:h, :w]))
    assert keys == [True] + [False] * (n - 1)
    assert len(set(qps)) >= n // 2, qps        # qp moved at least twice
    lumas = decode_luma(data, tmp_path / "served.h264", w, h)
    assert len(lumas) == n
    for c, (luma, ref) in enumerate(zip(lumas, refs)):
        assert np.array_equal(luma, ref), f"picture {c} is not the reference"


@pytest.mark.parametrize("kind", ["cabac_intra", "cabac_p"])
def test_token_ready_answers_for_a_cabac_token_and_changes_no_byte(
        encoder, kind):
    """``H264Encoder.token_ready``: ``is_ready()`` of the record stream's
    guessed prefix, which the collect pulls first.  A bool before the
    collect, True after it, no compile, one ``stats`` span a collected
    frame, and the access unit is still the reference coder's."""
    enc = encoder
    if kind == "cabac_intra":
        enc.request_keyframe()
    else:
        enc.encode_collect(enc.encode_submit(frame(40)))
    compiles, stats = (counter("jax_compile_cache_requests_total"),
                       samples("stats"))
    token = enc.encode_submit(frame(41))
    assert token[0] == kind
    assert enc.token_ready(token) in (True, False)
    want = reference_unit(enc, token)
    assert enc.encode_collect(token).data == want
    assert enc.token_ready(token) is True
    assert counter("jax_compile_cache_requests_total") == compiles
    assert samples("stats") == stats + 1


@pytest.mark.parametrize("w,h,fps,level", [
    (1280, 720, 30, 42), (1920, 1080, 60, 42), (2560, 1600, 60, 51),
    (3840, 2160, 30, 51)])
def test_stream_declares_the_level_its_size_and_refresh_need(
        w, h, fps, level, tmp_path):
    """H.264 Table A-1: the lowest level whose MaxFS and MaxMBPS hold the
    stream, never under 4.2 (every stream up to 1080p60 keeps its bytes).
    The muxer's codec string, which sizes a browser's hardware decoder,
    follows the SPS, and the independent decoder still takes the stream:
    one picture at the real size through the CAVLC reference coder (its
    levels all zero, so no device program runs), which decodes, at that
    size, under that level, to the flat grey such a picture is."""
    from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_entropy
    from docker_nvidia_glx_desktop_tpu.models import H264Encoder
    from docker_nvidia_glx_desktop_tpu.web.mp4 import Mp4Muxer, split_annexb

    assert syn.level_idc_for(w, h, fps) == level
    for profile, idc in (("main", 77), ("baseline", 66)):
        sps = syn.sps_rbsp(w, h, fps, profile=profile)
        assert (sps[0], sps[2]) == (idc, level)
    enc = H264Encoder(w, h, fps=fps)
    nals = split_annexb(enc.headers())
    sps = next(n for n in nals if n[0] & 0x1F == 7)
    pps = next(n for n in nals if n[0] & 0x1F == 8)
    assert Mp4Muxer(w, h, sps, pps, fps=fps).mime == (
        f'video/mp4; codecs="avc1.42C0{level:02X}"')

    def zeros(*shape):
        return np.zeros((enc.mb_h, enc.mb_w) + shape, np.int32)

    unit = h264_entropy.encode_intra_picture(
        dict(luma_dc=zeros(16), luma_ac=zeros(16, 15), cb_dc=zeros(4),
             cb_ac=zeros(4, 15), cr_dc=zeros(4), cr_ac=zeros(4, 15)),
        sps=enc._sps, pps=enc._pps, with_headers=True)
    lumas = decode_luma(unit, tmp_path / "grey.h264", w, h)
    assert len(lumas) == 1 and lumas[0].shape == (h, w)
    assert (lumas[0] == 128).all()


def test_codec_string_and_sdp_profile_follow_the_served_sps(encoder):
    """The muxer's codec string and the SDP's profile-level-id, from the
    SPS the encoder sends (tests/benchmark/test_benchmark_cabac.py reads
    its profile_idc and the PPS's entropy_coding_mode_flag bit by bit)."""
    from docker_nvidia_glx_desktop_tpu.web.mp4 import Mp4Muxer, split_annexb
    from docker_nvidia_glx_desktop_tpu.webrtc import sdp

    nals = split_annexb(encoder.headers())
    sps = next(n for n in nals if n[0] & 0x1F == 7)
    pps = next(n for n in nals if n[0] & 0x1F == 8)
    assert Mp4Muxer(W, H, sps, pps).mime == (
        f'video/mp4; codecs="avc1.4D{sps[2]:02X}{sps[3]:02X}"')
    assert sdp.h264_profile_level_id(sps) == "4d001f"
    assert sdp.h264_profile_level_id(b"\x67\x42\xe0\x1e") == "42e01f"
    assert sdp.h264_profile_level_id(None) == "42e01f"


@pytest.mark.parametrize("profile,pt", [("4d001f", 104), ("42e01f", 102)])
def test_sdp_answers_the_profile_of_the_stream(profile, pt):
    from docker_nvidia_glx_desktop_tpu.webrtc import sdp

    offer = "\r\n".join([
        "v=0", "o=- 1 2 IN IP4 127.0.0.1", "s=-", "t=0 0",
        "a=group:BUNDLE 0", "a=ice-ufrag:abcd",
        "a=ice-pwd:0123456789abcdef0123456789", "a=fingerprint:sha-256 " +
        ":".join(["AB"] * 32), "m=video 9 UDP/TLS/RTP/SAVPF 102 104",
        "c=IN IP4 0.0.0.0", "a=mid:0", "a=sendrecv", "a=rtcp-mux",
        "a=rtpmap:102 H264/90000",
        "a=fmtp:102 level-asymmetry-allowed=1;packetization-mode=1;"
        "profile-level-id=42e01f",
        "a=rtpmap:104 H264/90000",
        "a=fmtp:104 level-asymmetry-allowed=1;packetization-mode=1;"
        "profile-level-id=4d001f", ""])
    got = sdp.parse_offer(offer, h264_profile=profile)
    video = next(m for m in got.media if m.kind == "video")
    assert video.payload_type == pt and f"id={profile}" in video.fmtp
    answer = sdp.build_answer(got, "u", "p", "F", "candidate:1", "127.0.0.1",
                              {"video": 1}, h264_profile=profile)
    assert f"a=fmtp:{pt} " in answer and f"profile-level-id={profile}" in answer
    ours = sdp.build_offer("u", "p", "F", "candidate:1", "127.0.0.1",
                           {"video": 1, "audio": 2}, h264_profile=profile)
    assert f"profile-level-id={profile}" in ours


@pytest.mark.parametrize("qp", [20, 30, 41])
@pytest.mark.parametrize("kind", ["intra", "p"])
def test_traced_qp_twin_is_the_static_program_bit_for_bit(kind, qp):
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import h264_device, h264_inter
    from docker_nvidia_glx_desktop_tpu.utils.hostcolor import (
        rgb_to_yuv420_host)

    cur = rgb_to_yuv420_host(frame(3), H, W, float_fallback=True)
    if kind == "intra":
        want = h264_device.encode_intra_frame_yuv(*cur, qp)
        got = h264_device.encode_intra_frame_yuv_dynqp(*cur, np.int32(qp))
    else:
        ref = [jnp.asarray(p) for p in rgb_to_yuv420_host(
            frame(2), H, W, float_fallback=True)]
        want = h264_inter.encode_p_frame(*cur, *ref, qp=qp)
        ref = [jnp.asarray(p) for p in rgb_to_yuv420_host(
            frame(2), H, W, float_fallback=True)]       # the first is donated
        got = h264_inter.encode_p_frame_dynqp(*cur, *ref, np.int32(qp))
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


def test_no_compile_when_qp_or_the_record_stream_change(encoder):
    """After the warmed set-up and one IDR and P frame: other rungs of the
    rate ladder, the degrade bias, record streams from under one rung to
    over the guessed prefix (a second pull), and a new IDR, without one
    compile request."""
    enc = encoder
    enc.request_keyframe()
    for c in range(3):                  # IDR, P, and an IDR after a P
        if c == 2:
            enc.request_keyframe()
        enc.encode_collect(enc.encode_submit(frame(c)))
    requests = counter("jax_compile_cache_requests_total")
    extra = counter("dngd_encoder_pull_extra_total")
    fell = counter("dngd_encoder_cabac_fallback_total")
    seen = set()
    try:
        for c, (qp, noise) in enumerate(
                [(36, 2.0), (18, 40.0), (44, 1.0), (16, 50.0), (None, 10.0)]):
            enc._forced_qp = qp
            if c == 3:
                enc.request_keyframe()
            if qp is None:
                enc.degrade_qp_offset = 4       # the rate ladder, biased
            pull = enc._cabac_pull["intra" if c == 3 else "p"]
            ef = enc.encode_collect(enc.encode_submit(frame(3 + c, noise)))
            assert ef.keyframe == (c == 3)
            seen.add(pull.guess)
    finally:
        enc._forced_qp, enc.degrade_qp_offset = None, 0
    assert counter("jax_compile_cache_requests_total") == requests
    assert len(seen) >= 2, seen                 # the stream's length moved
    assert counter("dngd_encoder_pull_extra_total") > extra
    assert counter("dngd_encoder_cabac_fallback_total") == fell


@pytest.fixture(scope="module")
def served():
    """Frames served by a StreamSession under the deployment's environment
    at 128x96 (GOP 4: IDRs and P frames), and what the stage families and
    the two counters saw."""
    from docker_nvidia_glx_desktop_tpu.rfb.source import SyntheticSource
    from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

    sess = StreamSession(config(ENCODER_GOP="4", REFRESH="30"),
                         SyntheticSource(W, H, fps=30))
    posted, done = [], threading.Event()

    def post(frag, keyframe, fid=0):
        posted.append(keyframe)
        if len(posted) >= 9:
            done.set()

    sess._post = post
    names = obst.STAGES + obst.CABAC_STAGES
    before = {n: samples(n) for n in names}
    record = counter("dngd_encoder_cabac_record_bytes_total")
    fell = counter("dngd_encoder_cabac_fallback_total")
    link = {d: counter(f"dngd_encoder_{d}_bytes_total")
            for d in ("h2d", "d2h")}
    sess.start()
    try:
        assert done.wait(300), posted
    finally:
        sess.stop()
    return {"stages": {n: samples(n) - before[n] for n in names},
            "posted": list(posted), "mime": sess.hello()["mime"],
            "record": counter("dngd_encoder_cabac_record_bytes_total")
            - record,
            "link": {d: counter(f"dngd_encoder_{d}_bytes_total") - was
                     for d, was in link.items()},
            "fell": counter("dngd_encoder_cabac_fallback_total") - fell}


@pytest.mark.parametrize("name", [n for n in obst.STAGES + obst.CABAC_STAGES
                                  if n != "pull_extra"])
def test_a_served_cabac_frame_is_one_sample_of_every_stage(served, name):
    """The stages the cell's readers read (colour, dispatch, pull,
    assemble beside capture and the two halves of the turn) and the
    engine's own: once a frame."""
    frames = len(served["posted"])
    assert True in served["posted"][1:] and False in served["posted"]
    # the loop stops with up to PIPELINE_DEPTH frames submitted and not
    # yet collected, and polls the source once more
    assert frames <= served["stages"][name] <= frames + 3, served["stages"]


def test_a_served_cabac_frame_is_counted_and_never_falls_back(served):
    frames = len(served["posted"])
    # a record stream is its header and at least a record a macroblock
    assert served["record"] >= frames * 4 * (8 + NR + NR * NC // 4)
    assert served["fell"] == 0
    assert served["mime"].startswith('video/mp4; codecs="avc1.4D')


def test_a_served_cabac_frame_counts_its_bytes_over_the_link(served):
    """Host to device: the three planes of every dispatched frame, nothing
    else.  Device to host: at least the record stream the engine read (the
    guessed prefix has slack on top, and the content statistics ride
    beside it), and under the whole buffer a frame."""
    frames = len(served["posted"])
    planes = W * H * 3 // 2
    sent, rest = divmod(served["link"]["h2d"], planes)
    assert rest == 0 and frames <= sent <= frames + 3, served["link"]
    assert served["record"] < served["link"]["d2h"] < frames * 4 * 48_000


def test_a_dense_fallback_is_counted_and_codes_the_same_bytes(
        encoder, monkeypatch):
    from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac

    enc = encoder
    enc.request_keyframe()
    enc.encode_collect(enc.encode_submit(frame(0)))
    token = enc.encode_submit(frame(1))
    want = reference_unit(enc, token)
    dense = counter("dngd_encoder_cabac_fallback_total")
    monkeypatch.setattr(h264_cabac, "encode_p_from_binstream",
                        lambda *a, **kw: None)      # the engine's cap
    assert enc.encode_collect(token).data == want
    assert counter("dngd_encoder_cabac_fallback_total") == dense + 1


def test_without_the_native_engine_frames_count_as_python(
        monkeypatch, caplog):
    """No g++: an error at set-up, every frame counted, the same bytes."""
    from docker_nvidia_glx_desktop_tpu.native import lib as native_lib

    frames = [frame(c) for c in range(3)]
    enc = served_encoder(ENCODER_BITRATE_KBPS="0")
    assert enc._cabac_native
    want = [enc.encode_collect(enc.encode_submit(f)).data for f in frames]
    monkeypatch.setattr(native_lib, "has_cabac_engine", lambda: False)
    monkeypatch.setattr(native_lib, "has_cabac", lambda: False)
    with caplog.at_level("ERROR"):
        enc = served_encoder(ENCODER_BITRATE_KBPS="0")
    assert not enc._cabac_native
    assert sum("without the native engine" in r.message
               for r in caplog.records) == 1
    python = obsm.REGISTRY.get(
        "dngd_encoder_cabac_fallback_total").labels("python")
    n0 = python.value
    got = [enc.encode_collect(enc.encode_submit(f)).data for f in frames]
    assert python.value == n0 + 3
    assert got == want
