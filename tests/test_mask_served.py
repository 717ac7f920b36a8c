"""The damage-masked P frame as a per-frame path like the others (ISSUE 40):
the row program with ``qp`` traced (one compiled program a row bucket, the
static form's bytes), the set-up that compiles every bucket before a frame
is served, the path's stage spans and counters, and ``token_ready`` on its
token.  128x96: six macroblock rows, so the row program's buckets are 1, 2
and 4 and a plan of five rows or more is the full-frame program's."""

import numpy as np
import pytest

import conftest
from docker_nvidia_glx_desktop_tpu.models import h264
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg

W, H = 128, 96
ROWS = H // 16
MASK_COUNTERS = ("dngd_mask_rows_total", "dngd_mask_rows_damaged_total",
                 "dngd_mask_rows_coded_total",
                 "dngd_mask_rows_gathered_total")


def requests() -> float:
    return obsm.REGISTRY.get("jax_compile_cache_requests_total").value


def counts() -> dict:
    out = {name: obsm.REGISTRY.get(f"dngd_stage_{name}_ms")._default.count
           for name in obst.STAGES + obst.MASK_STAGES}
    out.update({name: obsm.REGISTRY.get(name).value
                for name in MASK_COUNTERS})
    out["pull_extra_total"] = obsm.REGISTRY.get(
        "dngd_encoder_pull_extra_total").value
    out["rows_frames"] = h264._M_MASK_FRAMES_ROWS.value
    out["dense_frames"] = h264._M_MASK_FRAMES_DENSE.value
    return out


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in counts().items()}


def dirty(base: np.ndarray, n_rows: int, seed: int) -> np.ndarray:
    """``base`` with fresh noise in its top ``n_rows`` macroblock rows."""
    out = base.copy()
    out[:16 * n_rows] = np.random.default_rng(seed).integers(
        0, 256, (16 * n_rows, W, 3), np.uint8)
    return out


@pytest.fixture(scope="module")
def encoder():
    """The served mask encoder (device CAVLC, tune=off, CBR), its row
    programs compiled by its own set-up and one IDR behind it."""
    enc = h264.H264Encoder(W, H, entropy="device",
                           host_color=True, gop=600, damage_mask=True,
                           deblock=True, bitrate_kbps=300, fps=60)
    assert enc._dyn_qp
    enc.warmed = enc.warm_pulls()
    enc.base = conftest.make_test_frame(H, W, seed=3)
    enc.encode_collect(enc.encode_submit(enc.base))
    return enc


def test_set_up_compiles_the_ladder_and_says_how_much(encoder):
    # the IDR, the full-frame P, a program a bucket, and the eight 64 KiB
    # slices of the one flat length
    assert dmg.bucket_ladder(ROWS) == [1, 2, 4]
    assert dmg.bucket_ladder(100) == [1, 2, 4, 8, 16, 32, 64]
    assert encoder.warmed == 3 + 2 + 8
    # a bucket's program carries the bucket in its name (the device trace
    # reads the rows a frame gathered off it), and is made once
    assert dmg.row_step(4) is dmg.row_step(4)
    assert dmg.row_step(4).__wrapped__.__name__ == "encode_p_rows_b4"


def test_nothing_compiles_over_the_ladders_of_qp_and_of_buckets(encoder):
    """Behind ``warm_pulls`` no bucket, no rung of the rate ladder and no
    length of the pull asks for a compile: ``qp`` is traced in the row
    program, and the flat buffer has one length for every bucket."""
    enc, before, n0 = encoder, counts(), requests()
    seen = set()
    try:
        for c, qp in enumerate(enc.ladder_qps()):
            n_rows = c % (ROWS + 1)
            enc._forced_qp = qp
            pull = enc._flat_pull["p"]
            pull.guess = (1 + c % 8) * pull.BUCKET
            token = enc.encode_submit(dirty(enc.base, n_rows, c))
            seen.add(token[4][0] if isinstance(token[4][0], str) else "p")
            assert len(enc.encode_collect(token).data) > 16
    finally:
        enc._forced_qp = None
    assert requests() == n0
    got = delta(before)
    assert seen == {"dmg", "p"} and got["rows_frames"] > got["dense_frames"] > 0


@pytest.mark.parametrize("qp", [20, 30, 44])
def test_the_traced_row_program_gives_the_static_ones_bytes(qp):
    """``row_step(1)`` against ``encode_p_rows`` (qp static, the
    form the hq tiers keep) on the same planes, references and worklist:
    the flat buffer, the scattered reference and the vectors, bit for bit."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

    assert "qp" not in dmg.ROW_STEP_DYNQP_STATIC
    r = np.random.default_rng(qp)
    y, ref_y = (r.integers(0, 256, (H, W), np.uint8) for _ in range(2))
    c, ref_c = (r.integers(0, 256, (H // 2, W // 2), np.uint8)
                for _ in range(2))
    hv, hl = cavlc_device.slice_header_slots(
        ROWS, W // 16, frame_num=1, qp_delta=qp - 26, slice_type=5,
        idr=False, deblocking_idc=2)
    rows = np.array([4], np.int32)
    args = lambda: (jnp.asarray(y), jnp.asarray(c), jnp.asarray(c),  # noqa: E731
                    jnp.asarray(ref_y), jnp.asarray(ref_c),
                    jnp.asarray(ref_c), jnp.asarray(rows),
                    jnp.asarray(hv[rows]), jnp.asarray(hl[rows]))
    n0 = requests()
    traced = dmg.row_step(1)(*args(), np.int32(qp), tune="off",
                             next_y=None, p_intra=False, deblock=True)
    static = dmg.encode_p_rows(*args(), qp, tune="off", next_y=None,
                               p_intra=False, deblock=True)
    for a, b in zip(traced[:5], static[:5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # three qps: three static programs, one traced
    assert requests() - n0 in (1, 2)
    assert np.asarray(traced[0])[4:8].view(np.uint32)[0] > 0   # it coded


@pytest.mark.parametrize("n_rows,program,short_guess", [
    (0, "rows", False), (1, "rows", False), (3, "rows", True),
    (5, "dense", False), (6, "dense", True)])
def test_a_masked_frame_is_one_sample_of_every_stage_and_counted(
        encoder, n_rows, program, short_guess):
    """damage_grid, colour, dispatch and pull once a planned P frame,
    whichever program codes it; pull_extra (span and counter) when the
    guessed prefix was short; rows damaged <= rows coded <= rows."""
    enc = encoder
    if short_guess:
        enc._flat_pull["p"].guess = 16
    before = counts()
    token = enc.encode_submit(dirty(enc.base, n_rows, 40 + n_rows))
    assert (token[4][0] == "dmg") == (program == "rows")
    ef = enc.encode_collect(token)
    got = delta(before)
    assert not ef.keyframe and len(ef.data) > 16
    extra = 1 if short_guess else 0
    assert {k: got[k] for k in ("damage_grid", "colour", "dispatch", "pull",
                                "pull_extra", "pull_extra_total")} == {
        "damage_grid": 1, "colour": 1, "dispatch": 1, "pull": 1,
        "pull_extra": extra, "pull_extra_total": extra}
    assert got["assemble"] == 0        # the muxer's part closes it
    rows = program == "rows"
    bucket = {0: 1, 1: 1, 3: 4}.get(n_rows, ROWS)
    assert got["dngd_mask_rows_total"] == ROWS
    assert got["dngd_mask_rows_damaged_total"] == (
        max(n_rows, 1) if rows else ROWS)
    assert got["dngd_mask_rows_coded_total"] == bucket
    assert got["dngd_mask_rows_gathered_total"] == (bucket if rows else 0)
    assert (got["rows_frames"], got["dense_frames"]) == (
        (1, 0) if rows else (0, 1))
    pull = enc._flat_pull["p"]
    assert pull.guess >= pull.BUCKET                   # the guess recovered


def test_an_idr_of_a_mask_session_is_planned_by_nothing(encoder):
    enc, before = encoder, counts()
    enc.request_keyframe()
    assert enc.encode_collect(enc.encode_submit(enc.base)).keyframe
    got = delta(before)
    assert got["damage_grid"] == 0 and got["colour"] == 1
    assert not any(got[k] for k in MASK_COUNTERS + ("rows_frames",
                                                     "dense_frames"))
    enc.encode_collect(enc.encode_submit(enc.base))


def test_token_ready_answers_for_a_masked_token_and_changes_no_byte():
    """``is_ready()`` of the row program's prefix: a bool before the
    collect and True after it, nothing compiled by the question, and the
    access unit is the one an encoder that was never asked gives."""
    units = []
    base = conftest.make_test_frame(H, W, seed=3)
    for ask in (True, False):
        enc = h264.H264Encoder(W, H, entropy="device",
                               host_color=True, gop=600, damage_mask=True)
        enc.encode_collect(enc.encode_submit(base))
        token, n0 = enc.encode_submit(dirty(base, 2, 9)), requests()
        assert token[0] == "p" and token[4][0] == "dmg"
        if ask:
            assert enc.token_ready(token) in (True, False)
            assert requests() == n0
        units.append(enc.encode_collect(token).data)
        if ask:
            assert enc.token_ready(token) is True
    assert units[0] == units[1]


def test_with_the_mask_off_no_mask_family_moves():
    enc = h264.H264Encoder(W, H, entropy="device",
                           host_color=True, gop=600, damage_mask=False)
    base = conftest.make_test_frame(H, W, seed=3)
    enc.encode_collect(enc.encode_submit(base))
    before = counts()
    enc.encode_collect(enc.encode_submit(dirty(base, 2, 9)))
    got = delta(before)
    assert got["dispatch"] == 1 and got["damage_grid"] == 0
    assert not any(got[k] for k in MASK_COUNTERS + ("rows_frames",
                                                     "dense_frames"))
