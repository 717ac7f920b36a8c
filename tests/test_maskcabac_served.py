"""The damage mask under the CABAC stream, as a per-frame path like the others
(ISSUE 43): ``ENCODER_ENTROPY=cabac`` + ``DNGD_DAMAGE_MASK=true`` through
``encode_submit`` / ``encode_collect``.

A planned P frame of at most the ladder's top goes through the row program of
its bucket (``ops/damage_mask.row_step_cabac``) and the binarizer over that
band, the engine codes the planned rows' slices and every other row leaves as
an all-skip CABAC slice; a plan past the ladder's top and every IDR take the
dense CABAC programs.  128x96: six macroblock rows, so the buckets are 1, 2
and 4 and a plan of five rows or more is dense; 144x80: nine macroblocks a
row (a width that is no multiple of 32) in five rows."""

import numpy as np
import pytest

import conftest
from docker_nvidia_glx_desktop_tpu.bitstream import h264 as syn
from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
from docker_nvidia_glx_desktop_tpu.models import h264
from docker_nvidia_glx_desktop_tpu.obs import metrics as obsm
from docker_nvidia_glx_desktop_tpu.obs import trace as obst
from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg
from docker_nvidia_glx_desktop_tpu.web.mp4 import split_annexb

W, H = 128, 96
ROWS = H // 16
COUNTERS = ("dngd_mask_rows_total", "dngd_mask_rows_damaged_total",
            "dngd_mask_rows_coded_total", "dngd_mask_rows_gathered_total",
            "dngd_encoder_pull_extra_total",
            "dngd_encoder_cabac_record_bytes_total",
            "dngd_encoder_d2h_bytes_total", "dngd_encoder_h2d_bytes_total")
STAGES = (obst.STAGES + obst.CABAC_STAGES + obst.MASK_STAGES
          + obst.MASK_CABAC_STAGES)


def requests() -> float:
    return obsm.REGISTRY.get("jax_compile_cache_requests_total").value


def counts() -> dict:
    out = {name: obsm.REGISTRY.get(f"dngd_stage_{name}_ms")._default.count
           for name in STAGES}
    out.update({name: obsm.REGISTRY.get(name).value for name in COUNTERS})
    out["rows_frames"] = h264._M_MASK_FRAMES_ROWS.value
    out["dense_frames"] = h264._M_MASK_FRAMES_DENSE.value
    out["skip_cache"] = h264_cabac._M_SKIP_CACHE.value
    out["skip_coded"] = h264_cabac._M_SKIP_CODED.value
    out["fallback_dense"] = h264._M_CABAC_DENSE.value
    return out


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in counts().items()}


def dirty(base: np.ndarray, rows, seed: int) -> np.ndarray:
    """``base`` with fresh noise in the macroblock rows ``rows``."""
    out = base.copy()
    r = np.random.default_rng(seed)
    for row in rows:
        out[16 * row:16 * row + 16] = r.integers(
            0, 256, (16, base.shape[1], 3), np.uint8)
    return out


def new_encoder(w=W, h=H, mask=True, **kw):
    kw = dict(dict(entropy="cabac", host_color=True, gop=600,
                   damage_mask=mask, deblock=True, bitrate_kbps=300, fps=60),
              **kw)
    return h264.H264Encoder(w, h, **kw)


def step(enc, rows, seed: int) -> np.ndarray:
    """The next picture of ``enc``'s stream: the one before with fresh noise
    in ``rows``, so that exactly those rows change."""
    enc.last = dirty(enc.last, rows, seed)
    return enc.last


def decoded_lumas(data: bytes, w: int, h: int, tmp_path) -> list:
    import cv2

    path = tmp_path / "stream.h264"
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


@pytest.fixture(scope="module")
def encoder():
    """The served masked CABAC encoder (tune=off, CBR), everything its frames
    can ask for compiled by its own set-up, and one IDR behind it."""
    enc = new_encoder()
    assert enc._dyn_qp and enc.cabac_device_binarize and enc._cabac_native
    enc.warmed = enc.warm_pulls()
    enc.base = enc.last = conftest.make_test_frame(H, W, seed=3)
    enc.encode_collect(enc.encode_submit(enc.base))
    return enc


def test_set_up_compiles_the_ladder_and_fills_the_skip_cache(encoder):
    assert dmg.bucket_ladder(ROWS) == [1, 2, 4]
    # a row program and a binarize a bucket, at least one slice a bucket's
    # record buffer, on top of the dense ladder's slices
    dense = new_encoder(mask=False).warm_pulls()
    assert encoder.warmed >= dense + 2 * 3 + 3
    assert dmg.row_step_cabac(4) is dmg.row_step_cabac(4)
    assert (dmg.row_step_cabac(4).__wrapped__.__name__
            == "encode_p_rows_cabac_b4")
    assert "cabac" in dmg.MASKED_ENTROPY and "device" in dmg.MASKED_ENTROPY
    # the all-skip slice's data at every qp: no frame codes one
    assert all(h264_cabac.skip_row_payload(W // 16, qp)[1]
               for qp in range(52))


def test_nothing_compiles_over_the_ladders_of_qp_and_of_buckets(encoder):
    """Behind ``warm_pulls`` no bucket, no rung of the rate ladder and no
    length of a pull asks for a compile: ``qp`` is traced in the row
    programs, absent from the binarize programs, and every bucket's pull
    ladder was walked."""
    enc, before, n0 = encoder, counts(), requests()
    seen = set()
    try:
        for c, qp in enumerate(enc.ladder_qps()):
            rows = list(range(c % (ROWS + 1)))
            enc._forced_qp = qp
            for bucket in dmg.bucket_ladder(ROWS):
                pull = enc._cabac_mask_pull(bucket)
                pull.guess = pull.rung(1 + (c % 3) * pull.BUCKET)
            token = enc.encode_submit(step(enc, rows, c))
            seen.add(token[0])
            assert len(enc.encode_collect(token).data) > 16
    finally:
        enc._forced_qp = None
    assert requests() == n0
    got = delta(before)
    assert seen == {"cabac_p_mask", "cabac_p"}
    assert got["rows_frames"] > got["dense_frames"] > 0
    assert got["skip_coded"] == 0 and got["skip_cache"] > 0
    assert got["fallback_dense"] == 0


# a script of P frames behind an IDR: (rows that change, the program)
SCRIPT = [((), "rows"),              # no damage: row 0 alone, bucket 1
          ((3,), "rows"),            # one row
          ((0, 2, 5), "rows"),       # scattered rows: bucket 4, one padded
          ((0, 1, 2, 3), "rows"),    # exactly the ladder's top
          ((0, 1, 2, 4, 5), "dense"),        # over it
          (tuple(range(ROWS)), "dense"),     # all rows
          ((4,), "rows"), ((1, 2), "rows")]


@pytest.mark.parametrize("w,h", [(W, H), (144, 80)])
def test_the_stream_decodes_to_the_encoders_own_reference(w, h, tmp_path):
    """IDR + the script's P frames, two frames in flight and qp moving:
    cv2's ffmpeg gives the encoder's reference picture after every frame,
    bit for bit, and every planned row is the dense CABAC encoder's slice
    for that row from the same reference, every other row the Python
    coder's all-skip slice."""
    rows_of = h // 16
    enc, dense_enc = new_encoder(w, h), new_encoder(w, h, mask=False)
    base = conftest.make_test_frame(h, w, seed=5)
    script = [(tuple(r for r in rows if r < rows_of), prog)
              for rows, prog in SCRIPT]
    pictures = [base]
    for i, (rows, _) in enumerate(script):     # each on the one before
        pictures.append(dirty(pictures[-1], rows, 70 + i))
    data, refs, units, tokens = enc.headers(), [], [], []
    states, kinds = [], []
    qps = [None, 30, 24, 36, 27, 33, 22, 40, 26]
    for c, rgb in enumerate(pictures):
        enc._forced_qp = qps[c]
        states.append(enc.export_state())
        tokens.append(enc.encode_submit(rgb))
        kinds.append(tokens[-1][0])
        refs.append(np.array(enc.export_state()["ref"][0]))
        if len(tokens) == 2:
            units.append(enc.encode_collect(tokens.pop(0)).data)
    units.append(enc.encode_collect(tokens.pop(0)).data)
    data += b"".join(units)
    assert kinds == ["cabac_intra"] + [
        "cabac_p_mask" if dmg._bucket_for(max(len(rows), 1), rows_of)
        < rows_of else "cabac_p" for rows, _ in script]
    assert {"cabac_p_mask", "cabac_p"} <= set(kinds)
    lumas = decoded_lumas(data, w, h, tmp_path)
    assert len(lumas) == len(pictures)
    assert [int(np.abs(a.astype(np.int16) - b[:h, :w]).max())
            for a, b in zip(lumas, refs)] == [0] * len(pictures)
    zero_mv = np.zeros((rows_of, w // 16, 2), np.int32)
    for c in range(1, len(pictures)):
        rows, _ = script[c - 1]
        dense_enc.import_state(states[c])
        dense_enc._force_idr = False           # (an import asks for an IDR)
        dense_enc._forced_qp = qps[c]
        want = split_annexb(dense_enc.encode(pictures[c]).data)
        got = split_annexb(units[c])
        assert len(got) == len(want) == rows_of
        planned = rows or (0,)
        assert [r for r in planned if got[r] != want[r]] == []
        if kinds[c] == "cabac_p_mask":
            skip = split_annexb(h264_cabac.encode_p_picture(
                {"mv": zero_mv, **zero_levels(rows_of, w // 16)},
                qp=qps[c], frame_num=c % 16, qp_delta=qps[c] - enc.qp,
                deblocking_idc=2, use_native=False))
            assert [r for r in range(rows_of)
                    if r not in planned and got[r] != skip[r]] == []


def zero_levels(nr: int, nc: int) -> dict:
    return {"luma": np.zeros((nr, nc, 16, 16), np.int32),
            "cb_dc": np.zeros((nr, nc, 4), np.int32),
            "cb_ac": np.zeros((nr, nc, 4, 15), np.int32),
            "cr_dc": np.zeros((nr, nc, 4), np.int32),
            "cr_ac": np.zeros((nr, nc, 4, 15), np.int32)}


@pytest.mark.parametrize("qp", range(52))
def test_the_all_skip_slice_is_the_python_coders(qp):
    """Every row of an all-skip picture (first, middle, last) at every
    ``frame_num``: the cached slice data behind the header the framing call
    writes is the pure-Python coder's slice, byte for byte; and the data
    depends on nothing but the qp."""
    nr, nc = 6, 8
    tail, _ = h264_cabac.skip_row_payload(nc, qp)
    src = np.frombuffer(tail, np.uint8)
    zeros = {"mv": np.zeros((nr, nc, 2), np.int32), **zero_levels(nr, nc)}
    for frame_num in range(16):
        got = syn.annexb_rows(
            src, np.zeros(nr, np.int64), np.full(nr, len(tail), np.int64),
            syn.NAL_SLICE, 2, mb_step=nc,
            slice_hdr=dict(slice_type=5, frame_num=frame_num, idr=False,
                           qp_delta=qp - 26, deblocking_idc=2, cabac=True,
                           cabac_init_idc=0))
        want = h264_cabac.encode_p_picture(
            zeros, qp=qp, frame_num=frame_num, qp_delta=qp - 26,
            deblocking_idc=2, use_native=False)
        assert got == want, frame_num
    assert len(split_annexb(want)) == nr
    assert 0 < len(tail) < 32


@pytest.mark.parametrize("rows,program,short_guess", [
    ((), "rows", False), ((2,), "rows", False), ((0, 3, 5), "rows", True),
    ((0, 1, 2, 3, 4), "dense", False)])
def test_a_masked_frame_is_one_sample_of_every_stage_and_counted(
        encoder, rows, program, short_guess):
    """damage_grid, colour, dispatch, pull and engine once a planned P
    frame whichever program codes it, skip_slices once a frame of the row
    program; pull_extra when the bucket's guess was short; the unplanned
    rows counted as slices from the cache; the record bytes and both
    directions of the link counted."""
    enc = encoder
    bucket = {0: 1, 1: 1, 3: 4}.get(len(rows), ROWS)
    if short_guess:
        enc._cabac_mask_pull(bucket).guess = 16
    before = counts()
    token = enc.encode_submit(step(enc, rows, 40 + len(rows)))
    assert (token[0] == "cabac_p_mask") == (program == "rows")
    assert enc.token_ready(token) in (True, False)
    ef = enc.encode_collect(token)
    assert enc.token_ready(token) is True
    got = delta(before)
    assert not ef.keyframe and len(split_annexb(ef.data)) == ROWS
    extra = 1 if short_guess else 0
    row_frame = program == "rows"
    assert {k: got[k] for k in (
        "damage_grid", "colour", "dispatch", "pull", "pull_extra",
        "dngd_encoder_pull_extra_total", "engine", "skip_slices")} == {
        "damage_grid": 1, "colour": 1, "dispatch": 1, "pull": 1,
        "pull_extra": extra, "dngd_encoder_pull_extra_total": extra,
        "engine": 1, "skip_slices": int(row_frame)}
    assert got["assemble"] == 0        # the muxer's part closes it
    planned = max(len(rows), 1)
    assert got["dngd_mask_rows_total"] == ROWS
    assert got["dngd_mask_rows_damaged_total"] == (
        planned if row_frame else ROWS)
    assert got["dngd_mask_rows_coded_total"] == bucket
    assert got["dngd_mask_rows_gathered_total"] == (
        bucket if row_frame else 0)
    assert (got["rows_frames"], got["dense_frames"]) == (
        (1, 0) if row_frame else (0, 1))
    assert got["skip_cache"] == (ROWS - planned if row_frame else 0)
    assert got["skip_coded"] == 0 and got["fallback_dense"] == 0
    assert got["dngd_encoder_cabac_record_bytes_total"] > 4 * (8 + bucket)
    assert got["dngd_encoder_d2h_bytes_total"] > 0
    # the planes and, on a frame of the row program, the worklist
    planes = W * H * 3 // 2
    assert got["dngd_encoder_h2d_bytes_total"] == planes + (
        4 * bucket if row_frame else 0)


def test_an_idr_of_a_masked_cabac_session_is_planned_by_nothing(encoder):
    enc, before = encoder, counts()
    enc.request_keyframe()
    token = enc.encode_submit(step(enc, (), 0))
    assert token[0] == "cabac_intra"
    assert enc.encode_collect(token).keyframe
    got = delta(before)
    assert got["damage_grid"] == 0 and got["skip_slices"] == 0
    assert not any(got[k] for k in COUNTERS[:4] + ("rows_frames",
                                                   "dense_frames"))
    enc.encode_collect(enc.encode_submit(step(enc, (), 0)))


@pytest.mark.parametrize("gives_way", ["stream_flag", "engine_cap"])
def test_the_dense_fallback_codes_the_same_frame_and_is_counted(
        gives_way, monkeypatch, tmp_path):
    """The record stream's overflow flag, or the engine's cap: the
    worklist's levels are scattered to the full frame and the host coder
    codes it whole — the bytes the row road gives, a stream the decoder
    follows to the encoder's reference — and the frame is counted."""
    base = conftest.make_test_frame(H, W, seed=3)
    pictures = [base, dirty(base, (1, 4), 7)]
    pictures.append(dirty(pictures[1], (2,), 8))
    twin = new_encoder()
    want = [twin.encode_collect(twin.encode_submit(p)).data
            for p in pictures]
    enc = new_encoder()
    data = enc.headers() + enc.encode_collect(enc.encode_submit(base)).data
    if gives_way == "engine_cap":
        monkeypatch.setattr(h264_cabac, "_engine_band", lambda *a: None)
    else:
        from docker_nvidia_glx_desktop_tpu.models.prefix_pull import (
            PrefixPull)
        monkeypatch.setattr(PrefixPull, "pull", lambda self, b, p: None)
    before, refs = counts(), []
    for c in (1, 2):
        token = enc.encode_submit(pictures[c])
        assert token[0] == "cabac_p_mask"
        au = enc.encode_collect(token).data
        assert au == want[c]
        data += au
        refs.append(np.array(enc.export_state()["ref"][0]))
    got = delta(before)
    assert got["fallback_dense"] == 2
    assert got["dngd_encoder_d2h_bytes_total"] > 0
    lumas = decoded_lumas(data, W, H, tmp_path)
    assert len(lumas) == 3
    assert [int(np.abs(a.astype(np.int16) - b).max())
            for a, b in zip(lumas[1:], refs)] == [0, 0]


@pytest.mark.parametrize("why", ["host_binarize", "keep_recon", "mesh",
                                 "mask_off", "hq"])
def test_the_plan_is_none_where_the_row_program_cannot_serve(why):
    """Host binarize (the level-pack transport), keep_recon's debug pulls, a
    spatial mesh (it gates rows instead), the mask off, a static qp: no
    plan, and the frame is the dense programs'."""
    kw = {"keep_recon": dict(keep_recon=True),
          "mask_off": dict(damage_mask=False),
          "hq": dict(tune="hq")}.get(why, {})
    enc = new_encoder(mask=kw.pop("damage_mask", True), **kw)
    if why == "host_binarize":
        enc._cabac_dev_bin = False      # the level transport, pinned
    assert enc.cabac_device_binarize == (why != "host_binarize")
    assert enc._dyn_qp == (why != "hq")
    if why == "mesh":
        enc._spatial_nx_cached = 2
    y = np.zeros((H, W), np.uint8)
    enc._damage_prev_y = enc._damage_cur_y = y
    assert enc._damage_plan(y) is None
    if why in ("host_binarize", "mask_off"):
        base = conftest.make_test_frame(H, W, seed=3)
        enc.encode_collect(enc.encode_submit(base))
        assert enc.encode_submit(dirty(base, (1,), 2))[0] == "cabac_p"


def test_the_served_encoder_plans_under_cabac():
    """... and where it can, the mask is not dropped: the plan of a calm
    frame is one row of the six."""
    enc = new_encoder()
    y = np.zeros((H, W), np.uint8)
    enc._damage_prev_y = enc._damage_cur_y = y
    plan = enc._damage_plan(y)
    assert plan is not None and (plan.bucket, plan.total) == (1, ROWS)


def test_the_row_programs_band_is_the_dense_programs_rows():
    """``row_step_cabac`` over a worklist against the dense P program, its
    bS inputs and its loop filter on the same planes and references: the
    vectors, the levels and the filtered recon of the worklist's rows, bit
    for bit; every other row of the reference untouched; and the band's
    record stream is the dense frame's rows' records."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.models.h264 import _cabac_bs_inputs
    from docker_nvidia_glx_desktop_tpu.ops import (cabac_binarize,
                                                   h264_deblock, h264_inter)

    r = np.random.default_rng(43)
    y, ref_y = (r.integers(0, 256, (H, W), np.uint8) for _ in range(2))
    c, ref_c = (r.integers(0, 256, (H // 2, W // 2), np.uint8)
                for _ in range(2))
    rows, qp = np.array([1, 4], np.int32), np.int32(31)
    fresh = lambda: (jnp.asarray(y), jnp.asarray(c), jnp.asarray(c),  # noqa: E731
                     jnp.asarray(ref_y), jnp.asarray(ref_c),
                     jnp.asarray(ref_c))
    ry, rcb, rcr, mv, lv = dmg.row_step_cabac(2)(
        *fresh(), jnp.asarray(rows), qp, deblock=True)
    out = h264_inter.encode_p_frame_dynqp(*fresh(), qp, tune="off")
    nnz, mv32 = _cabac_bs_inputs(out["luma"], out["mv"])
    dy, dcb, dcr = h264_deblock.deblock_frame_dynqp(
        out["recon_y"], out["recon_cb"], out["recon_cr"], qp, nnz_blk=nnz,
        mv=mv32)
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(out["mv"])[rows])
    for k in lv:
        np.testing.assert_array_equal(np.asarray(lv[k]),
                                      np.asarray(out[k])[rows])
    for got, dense, ref, size in ((ry, dy, ref_y, 16), (rcb, dcb, ref_c, 8),
                                  (rcr, dcr, ref_c, 8)):
        want = np.array(ref).reshape(ROWS, size, -1)
        want[rows] = np.asarray(dense).reshape(ROWS, size, -1)[rows]
        np.testing.assert_array_equal(
            np.asarray(got).reshape(ROWS, size, -1), want)
    band = np.asarray(cabac_binarize.binarize_p(
        mv, lv["luma"], lv["cb_dc"], lv["cb_ac"], lv["cr_dc"], lv["cr_ac"]))
    full = np.asarray(cabac_binarize.binarize_p(
        out["mv"], out["luma"], out["cb_dc"], out["cb_ac"], out["cr_dc"],
        out["cr_ac"]))
    pb, ob, bb = cabac_binarize.split_rows(band, 2)
    pf, of, bf = cabac_binarize.split_rows(full, ROWS)
    for i, row in enumerate(rows):
        assert bb[i] == bf[row] > 0
        np.testing.assert_array_equal(pb[ob[i]:ob[i + 1]],
                                      pf[of[row]:of[row + 1]])
