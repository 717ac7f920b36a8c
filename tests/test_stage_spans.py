"""Where the stage spans sit on the served per-frame path (obs/trace.stage
in web/session.py and models/h264.py), the set-up counters beside the
compile-cache listener, and the ``dngd.`` scopes in the device programs."""

import re
import threading

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import procstats
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
# (importing the session registers its families)
from docker_nvidia_glx_desktop_tpu.web import session as _  # noqa: F401

W, H = 128, 96
ENCODER_STAGES = ("colour", "dispatch", "pull", "pull_extra")
MARKS = ("capture", "captured", "device-submit", "device-collect",
         "bitstream", "publish")
# the session's own histograms of a turn (web/session.py, PR 38)
TURN_FAMILIES = ("dngd_session_turn_ms", "dngd_session_ready_wait_ms")


def counts() -> dict:
    """Samples so far in every stage family and the extra-pull counter."""
    out = {name: obsm.REGISTRY.get(f"dngd_stage_{name}_ms")._default.count
           for name in obst.STAGES + obst.TURN_STAGES}
    for name in TURN_FAMILIES:
        out[name] = obsm.REGISTRY.get(name)._default.count
    out["ws_send"] = obst.M_WS_SEND_MS._default.count
    out["pull_extra_total"] = obsm.REGISTRY.get(
        "dngd_encoder_pull_extra_total").value
    return out


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in counts().items()}


def frame(c: int) -> np.ndarray:
    """A soft texture panned by (c, 2c): motion search finds it, and an
    intra frame of it stays under the device entropy coder's cap."""
    yy, xx = np.mgrid[c:c + H, 2 * c:2 * c + W]
    v = 128 + 60 * np.sin(xx / 9.0) * np.cos(yy / 7.0) + 30 * np.sin(
        (xx + yy) / 3.0)
    return np.stack([v, v * 0.8 + 20, 255 - v], axis=-1).astype(np.uint8)


@pytest.fixture(scope="module")
def encoder():
    """The served encoder (device CAVLC, tune=off, qp traced) at 128x96,
    one IDR already behind it."""
    from docker_nvidia_glx_desktop_tpu.models import make_encoder

    cfg = from_env({"PASSWD": "pw", "SIZEW": str(W), "SIZEH": str(H),
                    "REFRESH": "30", "ENCODER_PREWARM": "false"})
    enc, _ = make_encoder(cfg, W, H)
    assert enc._dyn_qp and enc.entropy == "device"
    enc.encode_collect(enc.encode_submit(frame(0)))
    return enc


@pytest.mark.parametrize("kind", ["idr", "p"])
@pytest.mark.parametrize("short_guess", [False, True])
def test_each_frame_is_one_sample_of_every_encoder_stage(encoder, kind,
                                                         short_guess):
    """colour, dispatch and pull once a frame; pull_extra (span and
    counter) only when the guessed prefix was short."""
    enc = encoder
    if kind == "idr":
        enc._force_idr = True
    pull = enc._flat_pull["intra" if kind == "idr" else "p"]
    if short_guess:
        # the next frame's prefix holds the header and 16 bytes
        pull.guess = 16
    before = counts()
    ef = enc.encode_collect(enc.encode_submit(frame(3)))
    got = delta(before)
    assert ef.keyframe == (kind == "idr") and len(ef.data) > 16
    extra = 1 if short_guess else 0
    assert {k: got[k] for k in ENCODER_STAGES} == {
        "colour": 1, "dispatch": 1, "pull": 1, "pull_extra": extra}
    assert got["pull_extra_total"] == extra
    # the encoder's assembly is the first part of a split stage: the
    # session's muxer closes it (below), so no sample yet
    assert got["assemble"] == 0
    assert pull.guess >= pull.BUCKET  # the guess recovered


def test_dispatch_accounting_rides_the_dispatch_span(encoder):
    """One crossing a frame, its gap the span's own milliseconds; still
    one crossing, and no gap, with tracing off."""
    enc = encoder
    enc.pop_dispatch_sample()
    hist = obsm.REGISTRY.get("dngd_stage_dispatch_ms")._default
    s0 = hist.sum
    enc.encode_collect(enc.encode_submit(frame(4)))
    n, gap = enc.pop_dispatch_sample()
    assert n == 1 and gap == pytest.approx(hist.sum - s0)
    obst.set_enabled(False)
    try:
        enc.encode_collect(enc.encode_submit(frame(5)))
    finally:
        obst.set_enabled(True)
    assert enc.pop_dispatch_sample() == (1, 0.0)


def test_no_compile_when_qp_walks_the_ladder(encoder):
    """The steady window compiles nothing: after one IDR and one P frame
    the traced-qp programs serve every rung of the rate ladder, the
    degrade bias and a new IDR without one compile request."""
    enc = encoder
    for _ in range(2):                          # a P frame, warm
        enc.encode_collect(enc.encode_submit(frame(6)))
    compiles = obsm.REGISTRY.get("jax_compile_cache_requests_total")
    requests = compiles.value
    try:
        for c, qp in enumerate((36, 18, 44, 16, None)):
            enc._forced_qp = qp
            if c == 3:
                enc._force_idr = True
            if qp is None:
                enc.degrade_qp_offset = 4       # the rate ladder, biased
            ef = enc.encode_collect(enc.encode_submit(frame(7 + c)))
            assert ef.keyframe == (c == 3)
    finally:
        enc._forced_qp, enc.degrade_qp_offset = None, 0
    assert compiles.value == requests


def drive(enc, frames) -> list:
    """The session loop's pipelined shape at the encoder's own depth."""
    out, pend = [], []
    for f in frames:
        pend.append(enc.encode_submit(f))
        while len(pend) >= enc.pipeline_depth:
            out.append(enc.encode_collect(pend.pop(0)).data)
    while pend:
        out.append(enc.encode_collect(pend.pop(0)).data)
    return out


def content(kind: str, n: int = 9) -> list:
    """``noise``: every macroblock changes every frame.  ``calm``: a
    still picture with one 16x16 block of noise walking along the top."""
    r = np.random.default_rng(20)
    if kind == "noise":
        return [r.integers(0, 256, (H, W, 3), np.uint8) for _ in range(n)]
    out = []
    for i in range(n):
        f = frame(0)
        x0 = (16 * i) % (W - 16)
        f[0:16, x0:x0 + 16] = r.integers(0, 256, (16, 16, 3), np.uint8)
        out.append(f)
    return out


def raw_encoder(**options):
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    return H264Encoder(W, H, entropy="device",
                       host_color=True, gop=9, **options)


# Python->device crossings over one GOP of 9 frames (1 IDR + 8 P)
@pytest.mark.parametrize("options,kind,crossings", [
    ({}, "noise", 9),                           # the per-frame path
    ({"superstep_chunk": 4}, "noise", 1 + 2),   # the ring: one a chunk
    ({"damage_mask": True}, "calm", 9),         # the row worklist rides
    ({"damage_mask": True}, "noise", 9),        # the submit crossing
], ids=["per_frame", "ring", "masked_calm", "masked_full"])
def test_crossings_a_gop(options, kind, crossings):
    """One crossing a frame on the per-frame path, with or without the
    damage mask and whatever the damage; one a chunk on the ring."""
    enc = raw_encoder(**options)
    assert len(drive(enc, content(kind))) == 9
    assert enc._disp_count == crossings


def test_full_damage_through_the_mask_is_the_unmasked_stream():
    """Every row damaged: the masked encoder emits the mask-off bytes."""
    frames = content("noise")
    assert drive(raw_encoder(damage_mask=True), frames) == drive(
        raw_encoder(), frames)


@pytest.fixture(scope="module")
def served():
    """Frames served by a StreamSession at 128x96 (GOP 4: IDRs and P
    frames), with what the stage families and the pipeline recorder saw."""
    sess = small_session()
    marks, posted, done = [], [], threading.Event()
    listener = lambda kind, entry: marks.append((kind, entry))  # noqa: E731
    obst.tracer("pipeline").add_listener(listener)

    def post(frag, keyframe, fid=0):
        posted.append(keyframe)
        if len(posted) >= 9:
            done.set()

    sess._post = post
    waits, answers = spy_on_the_turn(sess)
    before, dropped = counts(), obst.dropped_total()
    sess.start()
    try:
        assert done.wait(300), posted
    finally:
        sess.stop()
        obst.tracer("pipeline").remove_listener(listener)
    return {"stages": delta(before), "posted": list(posted),
            "marks": marks, "dropped": obst.dropped_total() - dropped,
            "waits": len(waits), "answers": answers}


def small_session():
    """A StreamSession at 128x96, GOP 4 (IDRs and P frames), not started."""
    from docker_nvidia_glx_desktop_tpu.rfb.source import SyntheticSource
    from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

    cfg = from_env({"PASSWD": "pw", "SIZEW": str(W), "SIZEH": str(H),
                    "REFRESH": "30", "ENCODER_GOP": "4",
                    "ENCODER_PREWARM": "false"})
    return StreamSession(cfg, SyntheticSource(W, H, fps=30))


def spy_on_the_turn(sess):
    """Every turn that enters the end-of-turn wait, and every answer of
    the encoder's ``token_ready``, noted."""
    waits, answers = [], []
    wait, ready = sess._await_frame, sess.encoder.token_ready
    sess._await_frame = lambda *a: (waits.append(1), wait(*a))[1]
    sess.encoder.token_ready = (
        lambda token: (answers.append(ready(token)), answers[-1])[1])
    return waits, answers


@pytest.mark.parametrize("name", [n for n in obst.STAGES
                                  if n != "pull_extra"])
def test_a_served_frame_is_one_sample_of_every_stage(served, name):
    frames = len(served["posted"])
    assert True in served["posted"][1:] and False in served["posted"]
    got = served["stages"][name]
    # the loop stops with up to PIPELINE_DEPTH frames submitted and not
    # yet collected, and polls the source once more
    assert frames <= got <= frames + 3, (name, got, frames)
    assert served["stages"]["pull_extra"] == 0
    assert served["stages"]["pull_extra_total"] == 0


def test_a_served_frame_is_one_sample_of_stats_and_of_publish(served):
    """``stats`` a collected frame (the content statistics' pull that ends
    ``encode_collect``), ``publish`` a delivered one (the loop's tail)."""
    frames = len(served["posted"])
    assert served["stages"]["publish"] == frames
    assert frames <= served["stages"]["stats"] <= frames + 3
    assert served["stages"]["stats"] == served["stages"]["encode_collect"]


def test_a_turn_that_waits_is_one_sample_of_await(served):
    """One a turn that entered ``_await_frame`` and none of any other (a
    turn over the refresh has none: tests/test_session_pacing.py)."""
    assert served["stages"]["await"] == served["waits"]


def test_a_turn_that_took_a_frame_is_one_sample_of_the_turn(served):
    assert served["stages"]["dngd_session_turn_ms"] == \
        served["stages"]["encode_submit"]


def test_a_collected_frame_is_one_sample_of_the_ready_wait(served):
    """The per-frame CAVLC tokens answer a bool, so every collected frame
    has its sample; asked until the first yes and at the collect's start,
    six times a turn at most."""
    got = served["stages"]
    assert got["dngd_session_ready_wait_ms"] == got["encode_collect"]
    assert served["answers"] and set(served["answers"]) <= {True, False}
    assert len(served["answers"]) <= 6 * got["encode_submit"]


def test_with_tracing_off_no_new_family_moves_and_only_the_order_asks():
    """The one look left is the one a turn's order rests on (web/session.py:
    between the halves of the submit), once a frame at most."""
    sess = small_session()
    posted, done = [], threading.Event()
    sess._post = lambda *a, **k: (posted.append(1),
                                  len(posted) >= 5 and done.set())
    waits, answers = spy_on_the_turn(sess)
    obst.set_enabled(False)
    try:
        before = counts()
        sess.start()
        try:
            assert done.wait(300), posted
        finally:
            sess.stop()
        got = delta(before)
    finally:
        obst.set_enabled(True)
    assert len(posted) >= 5 and len(answers) <= len(posted) + 3
    new = obst.TURN_STAGES + TURN_FAMILIES
    assert {k: got[k] for k in new} == dict.fromkeys(new, 0)


@pytest.mark.parametrize("kind", ["idr", "p"])
def test_token_ready_answers_for_a_per_frame_token_and_changes_no_byte(
        encoder, kind):
    """``is_ready()`` of the prefix the collect pulls first: a bool before
    the collect and True after it, and the access unit is the one an
    encoder that was never asked gives."""
    from docker_nvidia_glx_desktop_tpu.models import make_encoder

    cfg = from_env({"PASSWD": "pw", "SIZEW": str(W), "SIZEH": str(H),
                    "REFRESH": "30", "ENCODER_PREWARM": "false"})
    units = []
    for ask in (True, False):
        enc, _ = make_encoder(cfg, W, H)
        enc._forced_qp = 30
        enc.encode_collect(enc.encode_submit(frame(0)))
        if kind == "idr":
            enc._force_idr = True
        compiles = obsm.REGISTRY.get("jax_compile_cache_requests_total")
        token, requests = enc.encode_submit(frame(3)), compiles.value
        assert token[0] == ("intra" if kind == "idr" else "p")
        if ask:
            assert enc.token_ready(token) in (True, False)
            assert compiles.value == requests
        units.append(enc.encode_collect(token).data)
        if ask:
            assert enc.token_ready(token) is True
    assert units[0] == units[1]


def test_token_ready_is_none_where_there_is_nothing_to_ask():
    """The ring path, a synchronous token, a marked token of no path or
    without its prefix, a prefix that has been deleted: None, and never an
    exception."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.models.base import Encoder

    enc, gone = raw_encoder(), jnp.zeros(8)
    assert Encoder.token_ready(enc, ("p", 0, 0.0, False, ())) is None
    assert enc.token_ready(("sync", None, None, True, object())) is None
    assert enc.token_ready(("ring", 1, 0.0, False, ({}, 0))) is None
    assert enc.token_ready(("p", 1, 0.0, False, ("dmg",) + (None,) * 7)) \
        is None
    assert enc.token_ready(("p", 1, 0.0, False, ("other",) + (gone,) * 7)) \
        is None
    assert enc.token_ready(("p", 1, 0.0, False, (None,) * 7)) is None
    assert enc.token_ready(None) is None
    payload = (30, 1, {}, None, None, gone, None)
    assert enc.token_ready(("p", 1, 0.0, False, payload)) is True
    gone.delete()
    assert enc.token_ready(("p", 1, 0.0, False, payload)) is None
    stacked = ("sp_bin", "p", 30, 0, 1, None, jnp.zeros((2, 8)), None)
    assert enc.token_ready(("cabac_p", 1, 0.0, False, stacked)) is True


def test_a_served_frames_marks_are_the_six_they_were(served):
    kinds = {k for k, _ in served["marks"]}
    assert kinds == {"marks"}
    for _, (fid, marks, pts, meta) in served["marks"]:
        assert tuple(stage for stage, _ in marks) == MARKS
    assert len(served["marks"]) == len(served["posted"])
    assert served["dropped"] == 0


def test_ws_send_closes_at_the_pump_after_the_write():
    """StreamSession._post stamps, the media pump observes once
    ``send_bytes`` has returned; an unstamped fragment (0.0) is skipped."""
    import asyncio
    import time

    from docker_nvidia_glx_desktop_tpu.web import server

    sent = []

    class Ws:
        async def send_bytes(self, data):
            await asyncio.sleep(0.002)
            sent.append(data)

        async def send_json(self, obj):
            sent.append(obj)

    async def go():
        q = asyncio.Queue()
        q.put_nowait(("frag", b"a", True, 0, time.perf_counter()))
        q.put_nowait(("frag", b"b", False, 0, 0.0))
        q.put_nowait(("frag", b"c", False))
        task = asyncio.ensure_future(server._pump_media(Ws(), q))
        while len(sent) < 3:
            await asyncio.sleep(0.001)
        task.cancel()

    hist = obst.M_WS_SEND_MS._default
    n0, s0 = hist.count, hist.sum
    asyncio.new_event_loop().run_until_complete(asyncio.wait_for(go(), 30))
    assert sent == [b"a", b"b", b"c"]
    assert hist.count == n0 + 1 and hist.sum - s0 >= 2.0


def test_post_stamps_only_while_tracing_is_on():
    from docker_nvidia_glx_desktop_tpu.rfb.source import SyntheticSource
    from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

    cfg = from_env({"PASSWD": "pw", "SIZEW": "64", "SIZEH": "48",
                    "WEBRTC_ENCODER": "x264enc", "ENCODER_PREWARM": "false"})
    sess = StreamSession(cfg, SyntheticSource(64, 48))
    q = sess.subscribe()
    while not q.empty():
        q.get_nowait()
    sess._post(b"x", True, 7)
    obst.set_enabled(False)
    try:
        sess._post(b"y", False, 8)
    finally:
        obst.set_enabled(True)
    on, off = q.get_nowait(), q.get_nowait()
    assert on[:4] == ("frag", b"x", True, 7) and on[4] > 0.0
    assert off == ("frag", b"y", False, 8, 0.0)


# -- set-up counters ----------------------------------------------------------

SETUP_COUNTERS = ("dngd_jax_trace_lower_seconds_total",
                  "dngd_jax_backend_compile_seconds_total",
                  "dngd_jax_cache_load_seconds_total")


def test_setup_counters_split_build_from_load():
    """The listeners beside the cache-hit listener: tracing and lowering,
    the backend's compile, and the cache's retrieval.  Compile-phase spans
    nest, and each counter takes a span's own time; the retrieval, which
    the backend-compile span brackets, is taken off that span."""
    from jax import monitoring

    assert procstats.register_jax_cache_listener()
    fam = {n: obsm.REGISTRY.get(n) for n in SETUP_COUNTERS}
    assert all(f.kind == "counter" and f.labelnames == ()
               for f in fam.values())
    v0 = {n: f.value for n, f in fam.items()}
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    span = monitoring.record_event_time_span
    import time
    t = time.time() + 1000.0        # after every span this thread has seen
    # an outer trace of 10 s that holds an inner trace of 2 s and an eager
    # operation's whole compile (0.5 + 0.25 + 1 s); then its own lowering,
    # and a backend compile that the cache serves in 2 of its 2.125 s
    span(trace, t + 1.0, t + 3.0)
    span(trace, t + 4.0, t + 4.5)
    span(lower, t + 4.5, t + 4.75)
    span(backend, t + 4.75, t + 5.75)
    span(trace, t, t + 10.0)
    span(lower, t + 10.0, t + 10.5)
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
    span(backend, t + 10.5, t + 12.625)
    span("/jax/some/other_duration", t, t + 100.0)
    monitoring.record_event_duration_secs(backend, 50.0)   # spans count
    got = {n: f.value - v0[n] for n, f in fam.items()}
    assert got == pytest.approx({
        "dngd_jax_trace_lower_seconds_total": 10.0 - 1.0 + 0.5,
        "dngd_jax_backend_compile_seconds_total": 1.0 + 0.125,
        "dngd_jax_cache_load_seconds_total": 2.0})
    assert sum(got.values()) == pytest.approx(12.625)      # wall clock


# -- named scopes in the device programs --------------------------------------

SCOPES = {
    "p": ("dngd.ingest", "dngd.me_int", "dngd.me_subpel", "dngd.mc",
          "dngd.tq", "dngd.recon", "dngd.slots", "dngd.pack",
          "dngd.deblock_bs"),
    "intra": ("dngd.intra", "dngd.slots", "dngd.pack"),
    "deblock": ("dngd.deblock_bs", "dngd.deblock_tile",
                "dngd.deblock_edges", "dngd.deblock_v", "dngd.deblock_h"),
    "colour": ("dngd.colour",),
    "frame_stats": ("dngd.frame_stats",),
    # the CABAC path's own five (PR 38; ENCODER_ENTROPY=cabac)
    "cabac_p": ("dngd.ingest", "dngd.me_int", "dngd.me_subpel", "dngd.mc",
                "dngd.tq", "dngd.recon"),
    "cabac_intra": ("dngd.intra",),
    "binarize_p": ("dngd.binarize",),
    "binarize_intra": ("dngd.binarize",),
    "cabac_bs": ("dngd.deblock_bs",),
    # a damage-mask session's row program (ISSUE 40): the shared stages
    # under their own names between the gather and the scatter
    "rows": ("dngd.mask_gather", "dngd.ingest", "dngd.me_int",
             "dngd.me_subpel", "dngd.mc", "dngd.tq", "dngd.recon",
             "dngd.slots", "dngd.pack", "dngd.deblock_bs",
             "dngd.deblock_tile", "dngd.deblock_edges", "dngd.deblock_v",
             "dngd.deblock_h", "dngd.mask_scatter"),
    # ... and the CABAC stream's (ISSUE 43): no slots and no pack, and the
    # binarizer over the worklist's band as a program of its own
    "rows_cabac": ("dngd.mask_gather", "dngd.ingest", "dngd.me_int",
                   "dngd.me_subpel", "dngd.mc", "dngd.tq", "dngd.recon",
                   "dngd.deblock_bs", "dngd.deblock_tile",
                   "dngd.deblock_edges", "dngd.deblock_v", "dngd.deblock_h",
                   "dngd.mask_scatter"),
    "binarize_band": ("dngd.binarize",),
    # ENCODER_TUNE=hq served whole under the loop filter (ISSUE 48): the two
    # encode programs with the plane and the chain under ``aq``, the P
    # program's decisions, the filter's threshold tile
    "hq_p": ("dngd.ingest", "dngd.aq", "dngd.me_int", "dngd.me_subpel",
             "dngd.mc", "dngd.tq", "dngd.recon", "dngd.mode_decision",
             "dngd.slots", "dngd.pack", "dngd.deblock_bs"),
    "hq_intra": ("dngd.intra", "dngd.aq", "dngd.slots", "dngd.pack"),
    "hq_deblock": ("dngd.deblock_bs", "dngd.deblock_thr",
                   "dngd.deblock_tile", "dngd.deblock_edges",
                   "dngd.deblock_v", "dngd.deblock_h"),
}


@pytest.fixture(scope="module")
def lowered(tmp_path_factory):
    """:func:`compiled_programs` from a process of its own, asked twice if
    the first one dies: XLA:CPU took a worker down inside
    ``backend_compile_and_load`` under this fixture (a segmentation fault
    and an abort, in two whole runs of PR 48's tree out of two; none when
    the module ran alone on six workers), and a worker that dies fails the
    run whatever the tests say.  A fresh process has compiled nothing
    before, which is what the fixture wants anyway."""
    import json
    import os
    import subprocess
    import sys

    out = tmp_path_factory.mktemp("lowered") / "programs.json"
    here = os.path.dirname(os.path.abspath(__file__))
    code = (f"import sys, json; sys.path[:0] = [{here!r}, "
            f"{os.path.dirname(here)!r}]; "
            "import conftest, test_stage_spans as t; "
            f"json.dump(t.compiled_programs(), open({str(out)!r}, 'w'))")
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", code], cwd=here,
                           capture_output=True, text=True, timeout=1200)
        if r.returncode == 0:
            break
    assert r.returncode == 0, r.stderr[-3000:]
    return {k: tuple(v) for k, v in json.loads(out.read_text()).items()}


def compiled_programs() -> dict:
    """The served programs (the ``_dynqp`` twins: the bodies are shared):
    the lowered text with debug info, and the operations' names in the
    compiled program.  At a geometry no other test compiles: within a
    process JAX hands a program it has compiled before the executable it
    had then, and its persistent cache leaves metadata out of its key, so
    an entry written by a tree without the scopes would be served here
    without them.  For these compiles the key holds the metadata too."""
    import jax

    from docker_nvidia_glx_desktop_tpu.models.h264 import (_cabac_bs_inputs,
                                                            _yuv_stage)
    from docker_nvidia_glx_desktop_tpu.ops import (
        cabac_binarize, cavlc_device, cavlc_p_device, content_stats,
        damage_mask, h264_deblock, h264_device, h264_inter)

    w, h = 176, 112
    nr, nc = h // 16, w // 16
    y = np.zeros((h, w), np.uint8)
    c = np.zeros((h // 2, w // 2), np.uint8)
    qp = np.int32(30)
    hv, hl = cavlc_device.slice_header_slots(nr, nc, frame_num=0)
    pv, pl = cavlc_device.slice_header_slots(
        nr, nc, frame_num=1, slice_type=5, idr=False)
    mv = np.zeros((nr, nc, 2), np.int8)
    progs = {
        "p": cavlc_p_device.encode_p_cavlc_frame_dynqp.lower(
            y, c, c, y, c, c, pv, pl, qp, "off", None, False),
        "intra": cavlc_device.encode_intra_cavlc_frame_yuv_dynqp.lower(
            y, c, c, hv, hl, qp, with_recon=True, i16_modes="auto",
            tune="off"),
        "deblock": h264_deblock.deblock_frame_dynqp.lower(
            y, c, c, qp, nnz_blk=np.zeros((nr, nc, 4, 4), bool), mv=mv),
        "colour": _yuv_stage.lower(np.zeros((h, w, 3), np.uint8), h, w),
        "frame_stats": content_stats.frame_stats.lower(
            y, y, y, mv, (), None, 512),
        # H264Encoder._submit_p_masked: a worklist of two of the seven rows
        "rows": damage_mask.row_step(2).lower(
            y, c, c, y, c, c, np.array([2, 5], np.int32), pv[[2, 5]],
            pl[[2, 5]], qp, tune="off", next_y=None, p_intra=False,
            deblock=True),
    }
    # the CABAC path as H264Encoder._submit_cabac_p / _submit_cabac_intra
    # hand it on: the level tensors of the P and the intra program into
    # the two binarize programs and the loop filter's inputs
    lv_p = jax.eval_shape(
        lambda *a: h264_inter.encode_p_frame_dynqp(*a, tune="off"),
        y, c, c, y, c, c, qp)
    lv_i = jax.eval_shape(
        lambda *a: h264_device.encode_intra_frame_yuv_dynqp(
            *a, i16_modes="auto", tune="off"), y, c, c, qp)
    zeros = lambda lv, *keys: [np.zeros(lv[k].shape, lv[k].dtype)  # noqa: E731
                               for k in keys]
    progs.update({
        "cabac_p": h264_inter.encode_p_frame_dynqp.lower(
            y, c, c, y, c, c, qp, tune="off"),
        "cabac_intra": h264_device.encode_intra_frame_yuv_dynqp.lower(
            y, c, c, qp, i16_modes="auto", tune="off"),
        "binarize_p": cabac_binarize.binarize_p.lower(*zeros(
            lv_p, "mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")),
        "binarize_intra": cabac_binarize.binarize_intra.lower(*zeros(
            lv_i, "luma_dc", "luma_ac", "cb_dc", "cb_ac", "cr_dc", "cr_ac",
            "pred_mode", "mb_i4", "i4_modes", "luma_i4")),
        "cabac_bs": _cabac_bs_inputs.lower(*zeros(lv_p, "luma", "mv")),
    })
    # H264Encoder._submit_cabac_p_masked: the same worklist through the
    # CABAC row step, its vectors and levels into the binarizer as a band
    rows_cabac = damage_mask.row_step_cabac(2)
    work = (y, c, c, y, c, c, np.array([2, 5], np.int32), qp)
    band = jax.eval_shape(lambda *a: rows_cabac(*a, deblock=True), *work)
    progs.update({
        "rows_cabac": rows_cabac.lower(*work, deblock=True),
        "binarize_band": cabac_binarize.binarize_p.lower(
            np.zeros(band[3].shape, band[3].dtype), *zeros(
                band[4], "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")),
    })
    # ... and none of them is written to the cache: nothing reads such an
    # entry but this fixture, and serializing one took a worker down with
    # a segmentation fault inside jaxlib (the driver's run of PR 46's tree)
    flags = {"jax_compilation_cache_include_metadata_in_key": True,
             "jax_persistent_cache_min_compile_time_secs": 1e9}
    was = {f: getattr(jax.config, f) for f in flags}
    for f, v in flags.items():
        jax.config.update(f, v)
    try:
        return {k: (low.as_text(debug_info=True),
                    re.findall(r'op_name="([^"]*)"',
                               low.compile().as_text()))
                for k, low in progs.items()}
    finally:
        for f, v in was.items():
            jax.config.update(f, v)


@pytest.fixture(scope="module")
def lowered_hq():
    """H264Encoder._submit_p_device / _submit_device / _deblock where hq is
    served whole (the plane of effective qps and the intra flags go from the
    P program's levels to the filter), LOWERED and not compiled: the text
    with debug info and the name stack of every equation in it (the
    ``op_name`` a compiled operation inherits).  Every worker that meets one
    of these tests builds this, and a compile of the P program is the dear
    part of the fixture above."""
    from docker_nvidia_glx_desktop_tpu.ops import (cavlc_device,
                                                   cavlc_p_device,
                                                   h264_deblock)

    w, h = 176, 112
    nr, nc = h // 16, w // 16
    y = np.zeros((h, w), np.uint8)
    c = np.zeros((h // 2, w // 2), np.uint8)
    qp = np.int32(30)
    hv, hl = cavlc_device.slice_header_slots(nr, nc, frame_num=0)
    pv, pl = cavlc_device.slice_header_slots(
        nr, nc, frame_num=1, slice_type=5, idr=False)
    progs = {
        "hq_p": cavlc_p_device.encode_p_cavlc_frame_dynqp.lower(
            y, c, c, y, c, c, pv, pl, qp, "hq", None, True, True),
        "hq_intra": cavlc_device.encode_intra_cavlc_frame_yuv_dynqp.lower(
            y, c, c, hv, hl, qp, with_recon=True, i16_modes="auto",
            tune="hq", with_qp_eff=True),
        "hq_deblock": h264_deblock.deblock_frame_dynqp.lower(
            y, c, c, qp, nnz_blk=np.zeros((nr, nc, 4, 4), bool),
            mv=np.zeros((nr, nc, 2), np.int8),
            qp_eff=np.full((nr, nc), 30, np.int32),
            mb_intra=np.zeros((nr, nc), bool)),
    }
    out = {}
    for k, low in progs.items():
        text = low.as_text(debug_info=True)
        out[k] = (text, re.findall(r'loc\("(jit\([^"]*)"', text))
    return out


HQ_PROGRAMS = sorted(p for p in SCOPES if p.startswith("hq_"))


@pytest.fixture
def either(request, program):
    """The compiled programs' fixture, or the hq programs' lowered one."""
    return request.getfixturevalue(
        "lowered_hq" if program in HQ_PROGRAMS else "lowered")


@pytest.mark.parametrize("program,scope", [
    (p, s) for p, scopes in SCOPES.items() for s in scopes])
def test_lowered_program_carries_the_scope(either, program, scope):
    lowered = either
    # (a loop body's names start anew at the scope inside it)
    assert re.search(rf'["/]{re.escape(scope)}/', lowered[program][0])


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_every_operation_lies_inside_a_scope(either, program):
    """In the compiled program every operation's name stack passes
    through some ``dngd.`` scope (the hq programs: every equation's, in
    the lowered text)."""
    names = either[program][1]
    assert len(names) > 10
    # (a name with no stack at all is a parameter or the body of a
    # reduction, which is no operation of its own)
    bare = sorted({n for n in names if "dngd." not in n and "/" in n})
    assert not bare, bare[:10]
