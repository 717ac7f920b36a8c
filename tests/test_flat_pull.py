"""The CAVLC flat buffer's pull (models/prefix_pull.FlatPull): its ladder
by numbers, the one second pull of a short guess, the checkpoint's two
guesses, and what the encoder's constructor builds and refuses (ISSUE 46,
asked again as ISSUE 47)."""

import numpy as np
import pytest

from docker_nvidia_glx_desktop_tpu.models import make_encoder
from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
from docker_nvidia_glx_desktop_tpu.models.prefix_pull import FlatPull
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.ops import cavlc_device
from docker_nvidia_glx_desktop_tpu.utils.config import from_env

KIB64 = 1 << 16
BASE = cavlc_device.META_WORDS * 4


def extra_pulls() -> tuple:
    """(the counter, the ``pull_extra`` span's samples) so far."""
    return (obsm.REGISTRY.get("dngd_encoder_pull_extra_total").value,
            obsm.REGISTRY.get("dngd_stage_pull_extra_ms")._default.count)


def flat_buffer(total_words: int, overflow: bool = False,
                length: int = BASE + 8 * KIB64) -> np.ndarray:
    """A flat buffer as the device writes it: the header's big-endian
    words ([0] the overflow flag, [1] the payload's words), then bytes."""
    words = np.zeros(cavlc_device.META_WORDS, ">u4")
    words[0], words[1] = overflow, total_words
    body = (np.arange(length - BASE) % 251).astype(np.uint8)
    return np.concatenate([words.view(np.uint8), body])


def test_the_ladder_is_64_kib_steps_under_the_max_of_the_last_eight():
    """First guesses of 4 (IDR) and 2 (P) buckets past a 4 KiB header;
    every later one is the largest of the last 8 needs, rounded up to a
    multiple of 64 KiB."""
    enc = H264Encoder(64, 48)
    idr, p = enc._flat_pull["intra"], enc._flat_pull["p"]
    assert (idr.hdrw, idr.BUCKET, idr.HISTORY) == (4096, 65536, 8)
    assert (idr.guess, p.guess) == (262144, 131072)
    assert [p.rung(n) for n in (1, 65536, 65537, 300000)] == [
        65536, 65536, 131072, 327680]
    p.note(300000)
    assert p.guess == 327680
    for _ in range(7):
        p.note(1000)
        assert p.guess == 327680           # held by the 8-frame max
    p.note(1000)
    assert p.guess == 65536                # ... and let go behind it
    assert idr.guess == 262144             # a kind of frame, a history


@pytest.mark.parametrize("shards", [0, 2])
def test_a_short_guess_pulls_the_needs_rung_once_and_counts_once(shards):
    """A prefix of 16 bytes under a frame of 160,000: ONE second pull, of
    the header and the need's rung (192 KiB), one count and one
    ``pull_extra`` span; the next guess covers it, and a prefix of that
    guess pulls once.  A mesh's stacked buffers: every shard at the
    longest's rung, still one count."""
    pull, rows = FlatPull(2), 3
    flat = flat_buffer(40000)
    if shards:
        flat = np.stack([flat_buffer(100), flat])
    before = extra_pulls()
    buf, meta = pull.pull(flat, flat[..., :BASE + 16], rows)
    assert buf.shape[-1] == BASE + 3 * KIB64
    assert np.array_equal(buf, flat[..., :BASE + 3 * KIB64])
    last = meta[-1] if shards else meta
    assert (last.total_words, last.overflow) == (40000, False)
    assert len(last.row_bytes) == rows
    if shards:
        assert [m.total_words for m in meta] == [100, 40000]
    assert extra_pulls() == (before[0] + 1, before[1] + 1)
    assert pull.guess == 3 * KIB64 and list(pull.hist) == [160000]
    buf, _ = pull.pull(flat, flat[..., :BASE + pull.guess], rows)
    assert buf.shape[-1] == BASE + 3 * KIB64
    assert extra_pulls() == (before[0] + 1, before[1] + 1)
    # the overflow flag ends the pull: nothing noted, nothing pulled again
    over = flat_buffer(40000, overflow=True)
    assert pull.pull(over, over[:BASE + 16], rows) is None
    assert list(pull.hist) == [160000, 160000]
    assert extra_pulls() == (before[0] + 1, before[1] + 1)


def test_both_guesses_round_trip_through_the_checkpoint():
    """``export_state`` carries ``pull_guess`` and ``p_pull_guess`` once a
    frame or a checkpoint has set them (the benchmark warms the ladder by
    writing them), ``import_state`` puts them back, and a fresh encoder's
    state leaves a warmed guess alone."""
    enc = H264Encoder(64, 48, gop=30)
    fresh = enc.export_state()
    assert (fresh["pull_guess"], fresh["p_pull_guess"]) == (None, None)
    enc.import_state(dict(fresh, pull_guess=5 * KIB64, p_pull_guess=KIB64))
    assert enc._flat_pull["intra"].guess == 5 * KIB64
    assert enc._flat_pull["p"].guess == KIB64
    state = enc.export_state()
    assert (state["pull_guess"], state["p_pull_guess"]) == (5 * KIB64, KIB64)
    other = H264Encoder(64, 48, gop=30)
    other.import_state(state)
    assert other._flat_pull["intra"].guess == 5 * KIB64
    assert other._flat_pull["p"].guess == KIB64
    other.import_state(fresh)
    assert other._flat_pull["intra"].guess == 5 * KIB64
    enc._flat_pull["p"].note(200000)       # a frame's need sets it too
    assert enc.export_state()["p_pull_guess"] == 4 * KIB64


def test_the_mesh_cavlc_collect_counts_its_second_pull():
    """A CAVLC frame over two shards whose guess was short: the stacked
    buffers are pulled again under a ``pull_extra`` span and
    ``dngd_encoder_pull_extra_total`` counts it, as on one chip."""
    enc = H264Encoder(64, 64, entropy="device", host_color=True,
                      spatial_shards=2)
    assert enc._spatial_nx == 2
    r = np.random.default_rng(46)
    rgb = r.integers(0, 256, (64, 64, 3)).astype(np.uint8)
    want = enc.encode(rgb).data            # the guess covers the frame
    enc.encode(rgb)                        # (idr_pic_id alternates)
    pull = enc._flat_pull["intra"]
    pull.guess = 16
    before = extra_pulls()
    token = enc.encode_submit(rgb)
    assert token[4][0] == "sp"
    got = enc.encode_collect(token).data
    assert extra_pulls() == (before[0] + 1, before[1] + 1)
    assert pull.guess == KIB64
    assert got == want and len(got) > 64   # either pull, the same bytes


@pytest.mark.parametrize("tune,deblock,device", [
    ("off", True, True), ("hq", True, True), ("hq_noaq", False, True),
    ("hq", False, False)])
def test_the_binarizer_is_placed_by_the_tune_and_no_variable(
        tune, deblock, device, monkeypatch):
    """The device binarizes unless the tune codes a qp a macroblock (``hq``
    with the loop filter off; under it ``hq`` is ``hq_noaq``), whatever
    ``ENCODER_CABAC_BINARIZE`` says: nothing reads it."""
    monkeypatch.setenv("ENCODER_CABAC_BINARIZE", "device" if not device
                       else "host")
    enc = H264Encoder(64, 48, entropy="cabac", tune=tune, deblock=deblock)
    assert enc.cabac_device_binarize is device
    assert enc._device_entropy is device
    if tune == "off":
        served, _ = make_encoder(from_env({
            "PASSWD": "pw", "SIZEW": "64", "SIZEH": "48",
            "ENCODER_ENTROPY": "cabac"}), 64, 48)
        assert served.cabac_device_binarize and served._dyn_qp


def test_encoder_entropy_native_is_refused_at_start_up():
    cfg = from_env({"PASSWD": "pw", "SIZEW": "64", "SIZEH": "48",
                    "ENCODER_ENTROPY": "native"})
    with pytest.raises(ValueError, match="unknown ENCODER_ENTROPY 'native'"):
        make_encoder(cfg, 64, 48)
    with pytest.raises(ValueError, match="unknown entropy 'native'"):
        H264Encoder(64, 48, entropy="native")


def test_the_constructor_builds_the_cavlc_encoder_and_no_other_mode():
    """``mode`` is a checked argument and nothing else: any value but
    ``"cavlc"`` is a ValueError, and nothing is stored."""
    for mode in ("pcm", "cabac", None):
        with pytest.raises(ValueError, match="unknown h264 mode"):
            H264Encoder(64, 48, mode=mode)
    enc = H264Encoder(64, 48)
    named = H264Encoder(64, 48, mode="cavlc")
    assert not hasattr(enc, "mode")
    assert enc.entropy == "device" and enc._dyn_qp
    assert enc.headers() == named.headers()
    assert enc._sps[0] == 66               # Baseline: a CAVLC stream
