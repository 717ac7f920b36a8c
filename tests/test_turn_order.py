"""The stream does not depend on the order a turn of the session loop took
(web/session.py, PR 39): a frame collected BETWEEN the halves of the next
frame's submit (``H264Encoder.encode_submit`` calls the session's
``between_halves`` there) and one collected behind the whole submit give the
same access units, because the next frame's qp is reserved in the first half
either way.  The real encoder
(128x96, CAVLC and CABAC, rate control on and walking) under the real
``StreamSession._run``, on the pacing tests' fake clock.  ``mask``: the
CAVLC encoder under ``DNGD_DAMAGE_MASK`` on pictures of which only the top
rows change, so that its P frames are the row program's (ISSUE 40: the row
plan is made in the first half, and a mask session's turn has the two
halves of any other); ``cabac_mask``: the same pictures through the CABAC
encoder under the mask (ISSUE 43), whose P frames are ``cabac_p_mask``
tokens: ``token_ready`` answers by their prefix, so the early collect takes
them as it takes any other."""

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.models import make_encoder
from docker_nvidia_glx_desktop_tpu.resilience import faults
from docker_nvidia_glx_desktop_tpu.utils.config import from_env
from docker_nvidia_glx_desktop_tpu.web import session as session_mod
from test_session_pacing import drive

W, H = 128, 96
FRAMES = 14


def frame(c: int) -> np.ndarray:
    """A texture panned by (c, 2c) whose contrast grows with ``c``: the
    frames' sizes differ, and the rate controller walks."""
    yy, xx = np.mgrid[c:c + H, 2 * c:2 * c + W]
    v = 128 + (40 + 6 * c) * np.sin(xx / 5.0) * np.cos(yy / 4.0) \
        + 30 * np.sin((xx + yy) / 3.0)
    return np.stack([v, v * 0.8 + 20, 255 - v],
                    axis=-1).clip(0, 255).astype(np.uint8)


def calm_frame(c: int) -> np.ndarray:
    """``frame(c)`` in the top one to four macroblock rows, ``frame(0)``
    under them: a damage plan of a bucket below the picture's six rows."""
    out = frame(0)
    rows = 16 * (1 + c % 4)
    out[:rows] = frame(c)[:rows]
    return out


MASKS = {"mask": "device", "cabac_mask": "cabac"}


def pictures(kind: str):
    return calm_frame if kind in MASKS else frame


def new_encoder(kind: str):
    """``device`` or ``cabac``: the entropy coder; ``mask``: ``device``
    under the damage mask; ``cabac_mask``: ``cabac`` under it (ISSUE 43:
    the row program of the CABAC stream, a token kind of its own)."""
    mask = kind in MASKS
    cfg = from_env({"PASSWD": "pw", "SIZEW": str(W), "SIZEH": str(H),
                    "REFRESH": "60",
                    "ENCODER_ENTROPY": MASKS.get(kind, kind),
                    "ENCODER_BITRATE_KBPS": "100" if mask else "300",
                    "ENCODER_GOP": "60", "ENCODER_PREWARM": "false"})
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DNGD_DAMAGE_MASK", "true" if mask else "false")
        enc, _ = make_encoder(cfg, W, H)
    assert enc._dyn_qp and enc._rate is not None
    assert enc.damage_mask == mask
    return enc


def front(enc, early: bool, fail_at=None, frame=frame):
    """The session's view of ``enc``: the source's frame ``k`` is
    ``frame(k)``, and ``token_ready`` says ``early`` whatever the device
    does, so every owed collect goes in front of the dispatch, or none.
    ``fail_at``: the collect owed in the turn of frame ``fail_at`` meets an
    injected timeout."""

    class Front:
        pipeline_depth = 2
        between_halves = None

        def __init__(self, clock, cost):
            self.taken, self.calls = [], []

        def between(self):
            self.calls.append("between")
            self.between_halves()

        def encode_submit(self, k):
            self.taken.append(k)
            self.calls.append("submit")
            if k == fail_at and k not in self.taken[:-1]:
                faults.arm("collect_timeout", count=1)
            enc.between_halves = self.between
            try:
                return enc.encode_submit(frame(k))
            finally:
                enc.between_halves = None
                self.calls.append("submitted")

        def token_ready(self, token):
            assert enc.token_ready(token) in (True, False)
            return early

        def encode_collect(self, token):
            self.calls.append("collect")
            return enc.encode_collect(token)

        def request_keyframe(self):
            enc.request_keyframe()

        def export_state(self):
            return {}

    return Front


def serve(monkeypatch, entropy, early, fail_at=None):
    """FRAMES frames through ``StreamSession._run``: the access units the
    AU listeners were handed, the calls, and the encoder afterwards."""
    enc = new_encoder(entropy)
    aus, programs = [], mask_frames()

    def prepare(sess):
        sess._au_listeners.append(
            lambda data, key, pts: aus.append((key, bytes(data))))

    n0 = session_mod._M_EARLY_COLLECTS.value
    f0 = session_mod._M_COLLECT_FAIL.value
    try:
        run = drive(monkeypatch, seconds=(FRAMES - 0.5) / 60.0,
                    work=front(enc, early, fail_at, pictures(entropy)),
                    prepare=prepare)
    finally:
        faults.disarm_all()
    run.collect_failures = session_mod._M_COLLECT_FAIL.value - f0
    run.row_frames = mask_frames()["rows"] - programs["rows"]
    return aus, run, enc, session_mod._M_EARLY_COLLECTS.value - n0


def mask_frames() -> dict:
    """P frames a damage plan has sent to each program so far."""
    from docker_nvidia_glx_desktop_tpu.models import h264
    return {"rows": h264._M_MASK_FRAMES_ROWS.value,
            "dense": h264._M_MASK_FRAMES_DENSE.value}


@pytest.fixture(scope="module",
                params=["device", "cabac", "mask", "cabac_mask"])
def both_orders(request):
    """One run forced to the early order and one forced to today's."""
    mp = pytest.MonkeyPatch()
    try:
        return (request.param, serve(mp, request.param, True),
                serve(mp, request.param, False))
    finally:
        mp.undo()


def test_both_orders_give_the_same_access_units(both_orders):
    _, (early, run_e, enc_e, n_e), (late, run_l, enc_l, n_l) = both_orders
    assert run_e.taken == run_l.taken and len(run_e.taken) >= FRAMES - 1
    assert early == late and len(early) >= FRAMES - 2
    assert [key for key, _ in early] == [True] + [False] * (len(early) - 1)
    assert n_l == 0 and n_e == len(run_e.taken) - 1
    # the controller ended where it ended, too
    assert enc_e._rate.level == enc_l._rate.level
    assert enc_e._rate.pending_count == enc_l._rate.pending_count
    # ... and under the mask every P frame was the row program's
    rows = len(run_e.taken) - 1 if both_orders[0] in MASKS else 0
    assert run_e.row_frames == run_l.row_frames == rows


def test_the_turns_took_the_orders_they_were_forced_to(both_orders):
    _, (_, run_e, _, _), (_, run_l, _, _) = both_orders
    text = " ".join(run_e.work.calls)
    assert text.startswith("submit between submitted "
                           "submit between collect submitted")
    assert "submitted collect" not in text
    text = " ".join(run_l.work.calls)
    assert text.startswith("submit between submitted "
                           "submit between submitted collect")
    assert "between collect" not in text


def test_the_qp_walked_so_the_order_could_have_shown(both_orders):
    entropy, (early, _, enc, _), _ = both_orders
    sizes = [len(data) for _, data in early]
    assert len(set(sizes[1:])) > 3
    assert enc._rate._step_idx != enc._rate.STEPS.index(0)


@pytest.mark.parametrize("entropy", ["device", "cabac", "mask",
                                     "cabac_mask"])
def test_a_hook_that_does_nothing_changes_no_token_and_no_byte(entropy):
    """``encode_submit`` is the two halves back to back, with or without a
    caller between them."""
    plain, hooked, calls = new_encoder(entropy), new_encoder(entropy), []
    hooked.between_halves = lambda: calls.append(hooked._rate.pending_count)
    frame = pictures(entropy)
    for k in range(5):
        a, b = plain.encode_submit(frame(k)), hooked.encode_submit(frame(k))
        assert a[:2] == b[:2] and a[3] == b[3]
        ea, eb = plain.encode_collect(a), hooked.encode_collect(b)
        assert ea.data == eb.data and ea.keyframe == eb.keyframe == (k == 0)
    # called once a frame, behind the frame's own qp reservation
    assert calls == [1] * 5
    assert plain._rate.level == hooked._rate.level


@pytest.mark.parametrize("one_piece", ["ring", "masked_ring", "sync"])
def test_a_one_piece_submit_calls_nothing_between(one_piece):
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    kw = {"ring": dict(entropy="device", gop=30, superstep_chunk=4),
          "masked_ring": dict(entropy="device", gop=30, superstep_chunk=4,
                              damage_mask=True, host_color=True),
          "sync": dict(entropy="python", gop=30)}[one_piece]
    enc = H264Encoder(W, H, bitrate_kbps=300, fps=60, **kw)
    calls = []
    enc.between_halves = lambda: calls.append(1)
    tokens = [enc.encode_submit(frame(k)) for k in range(2)]
    assert not calls and [len(enc.encode_collect(t).data) > 16
                          for t in tokens] == [True, True]


@pytest.fixture(scope="module")
def failed_collects():
    """A ``collect_timeout`` injected into the collect owed in frame 6's
    turn: in the early order it is the one between the halves."""
    mp = pytest.MonkeyPatch()
    try:
        return (serve(mp, "device", True, fail_at=6),
                serve(mp, "device", False, fail_at=6))
    finally:
        mp.undo()


def test_a_failed_early_collect_ends_as_todays_does(failed_collects):
    (early, run_e, enc_e, _), (late, run_l, enc_l, _) = failed_collects
    assert run_e.collect_failures == run_l.collect_failures == 1
    # the frame already begun was dispatched all the same (the failed
    # collect never reached the encoder: a turn of begin, dispatch); the
    # IDR's turn collects it, a stale P, between its halves, and the loop
    # drains the IDR at once, as it does today ...
    calls = run_e.work.calls
    assert calls.count("submit") == calls.count("submitted")
    assert ("collect submitted submit between submitted "
            "submit between collect submitted collect "
            "submit between submitted submit between collect"
            ) in " ".join(calls)
    # ... and dropped with the failed one: frames 5 and 6 are missing, one
    # resync IDR follows, and the stream is the one today's order gives
    for aus in (early, late):
        keys = [key for key, _ in aus]
        assert keys.count(True) == 2 and keys[0] and keys[5]
    assert early == late and run_e.taken == run_l.taken
    # the rate controller holds no reservation that no collect will take
    assert enc_e._rate.pending_count == enc_l._rate.pending_count <= 2


@pytest.mark.parametrize("half", ["first", "between", "second"])
def test_a_failing_half_rolls_the_reservation_back(half):
    enc = new_encoder("device")
    tokens = [enc.encode_submit(frame(k)) for k in range(2)]
    enc.encode_collect(tokens[0])
    assert enc._rate.pending_count == 1

    def boom(*a, **k):
        raise RuntimeError("no device")

    def collect_then(then=lambda: None):
        # a collect between the halves takes the OLDEST reservation
        assert enc._rate.pending_count == 2
        enc.encode_collect(tokens[1])
        then()

    if half == "first":
        enc._planes_device = boom
    elif half == "between":
        enc.between_halves = lambda: collect_then(boom)
    else:
        enc.between_halves = collect_then
        enc._submit_p_device = boom
    with pytest.raises(RuntimeError):
        enc.encode_submit(frame(2))
    assert enc._rate.pending_count == (1 if half == "first" else 0)
    assert enc._force_idr
