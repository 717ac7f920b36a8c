"""H.264 intra (I_16x16) transform/quant/recon stage on device.

TPU-first design (SURVEY.md §2.3 "intra-frame parallelism"): the reference
encodes inside NVENC silicon with wavefront MB pipelines; we instead make
each macroblock **row** its own slice, which legalizes full row parallelism
— intra prediction then only ever references the MB to the left, so the
frame is a `vmap` over rows crossed with a 120-step `lax.scan` along the
row (1080p).  Each scan step processes one MB column across all rows: 68
MBs of 4x4 integer DCTs, Hadamard DC, quant, and normative reconstruction,
all batched int32 VPU work that XLA fuses into a handful of kernels.

Prediction uses DC mode only (Intra16x16PredMode=2, chroma DC mode 0):
with the top row in another slice, the only available reference is the
left MB's reconstructed right column, carried through the scan.  The
reconstruction here is bit-exact against conformant decoders (verified in
tests by decoding our stream with FFmpeg-backed cv2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import color, quant
from .dct import fdct4x4 as _fwd4x4
from .dct import hadamard2x2 as _had2
from .dct import hadamard4x4 as _had4
from .dct import idct4x4 as _inv4x4

# Zigzag scan for 4x4 blocks (raster index at each scan position).
ZIGZAG4 = np.array([0, 1, 4, 8, 5, 2, 3, 6, 9, 12, 13, 10, 7, 11, 14, 15],
                   dtype=np.int32)

# luma4x4BlkIdx -> (bx, by) in 4-sample units (spec §6.4.3).
LUMA_BLOCK_ORDER = np.array(
    [(0, 0), (1, 0), (0, 1), (1, 1),
     (2, 0), (3, 0), (2, 1), (3, 1),
     (0, 2), (1, 2), (0, 3), (1, 3),
     (2, 2), (3, 2), (2, 3), (3, 3)], dtype=np.int32)


def nnz_blocks_raster(luma_zz):
    """(R, C, 16 blkIdx, 16) zigzag P-luma levels -> (R, C, 4, 4) raster
    nonzero-4x4-block mask (the deblock filter's bS input)."""
    nnz_zz = (luma_zz != 0).any(axis=-1)
    nr, nc = nnz_zz.shape[:2]
    return jnp.zeros((nr, nc, 4, 4), bool).at[
        :, :, LUMA_BLOCK_ORDER[:, 1], LUMA_BLOCK_ORDER[:, 0]].set(nnz_zz)


def _blocks(mb, n):
    """(..., 16|8, 16|8) MB -> (..., n/4?, ...) -> (..., by, bx, 4, 4)."""
    s = mb.shape
    b = mb.reshape(s[:-2] + (n, 4, n, 4))
    return jnp.moveaxis(b, -2, -3)  # (..., by, bx, 4, 4)


def _unblocks(b):
    """Inverse of :func:`_blocks`."""
    s = b.shape
    m = jnp.moveaxis(b, -3, -2)  # (..., by, 4, bx, 4)
    return m.reshape(s[:-4] + (s[-4] * 4, s[-3] * 4))


def _i16_candidate(ymb, pred, qp):
    """Transform/quant/recon one I16 prediction candidate.

    Returns (ac (R,4,4,4,4), dcl (R,4,4), recon (R,16,16), bits (R,))."""
    res = ymb - pred
    w = _fwd4x4(_blocks(res, 4))                      # (R, by, bx, 4, 4)
    dc = w[..., 0, 0]                                 # (R, by, bx)
    ac = quant.h264_quantize_4x4(w, qp, intra=True)
    ac = ac.at[..., 0, 0].set(0)

    wd2 = _had4(dc)
    wd = jnp.sign(wd2) * (jnp.abs(wd2) >> 1)          # /2, truncate to zero
    dcl = quant.h264_quantize_luma_dc(wd, qp)

    # normative reconstruction
    fd = _had4(dcl)
    dcy = quant.h264_dequantize_luma_dc(fd, qp)
    wr = quant.h264_dequantize_4x4(ac, qp)
    wr = wr.at[..., 0, 0].set(dcy)
    resr = _inv4x4(wr)
    recon = jnp.clip(pred + _unblocks(resr), 0, 255)
    bits = (_level_bits_est(ac, (1, 2, 3, 4))
            + _level_bits_est(dcl, (1, 2)))
    return ac, dcl, recon, bits


def _ssd(recon, src, axes):
    d = recon - src
    return (d * d).sum(axis=axes)


def _luma_step(ymb, left_col, has_left, qp, allow_h: bool = False,
               lam=None):
    """One MB column of luma across all rows.

    ymb: (R, 16, 16) int32; left_col: (R, 16) recon right column of left MB.
    Returns (ac_levels (R,4,4,4,4), dc_levels (R,4,4), recon (R,16,16),
    mode (R,) Intra16x16PredMode — 2 = DC, 1 = Horizontal — and the
    chosen candidate's score (R,), the I16-vs-I4 decision input).

    With ``allow_h`` the per-MB decision codes BOTH candidates and keeps
    the better one.  ``lam is None`` (tune=off) scores by estimated
    CAVLC bits alone (a SAD decision measurably mis-picks: structured
    residuals cost fewer bits than their SAD suggests); with ``lam``
    (tune=hq) the score is the Lagrangian SSD + lam * bits, so the
    decision stops ignoring the distortion it is buying.  H copies the
    left MB's reconstructed right column across each row (the only
    directional I16 mode available under slice-per-row), nailing content
    constant along x — window chrome, toolbars, text rows.
    """
    psum = (jnp.sum(left_col, axis=-1) + 8) >> 4
    pred_dc = jnp.where(has_left, psum, 128)[:, None, None]   # (R, 1, 1)
    pred_dc = jnp.broadcast_to(pred_dc, ymb.shape)
    ac, dcl, recon, bits = _i16_candidate(ymb, pred_dc, qp)
    if lam is not None:
        score = _ssd(recon, ymb, (1, 2)).astype(jnp.float32) + lam * bits
    else:
        score = bits
    mode = jnp.full(ymb.shape[:1], 2, jnp.int32)
    if allow_h:
        pred_h = jnp.broadcast_to(left_col[:, :, None], left_col.shape + (16,))
        ac_h, dcl_h, recon_h, bits_h = _i16_candidate(ymb, pred_h, qp)
        if lam is not None:
            score_h = (_ssd(recon_h, ymb, (1, 2)).astype(jnp.float32)
                       + lam * bits_h)
            use_h = has_left & (score_h < score)
            score = jnp.minimum(score,
                                jnp.where(has_left, score_h, jnp.inf))
        else:
            use_h = has_left & (bits_h < score)
            score = jnp.minimum(score,
                                jnp.where(has_left, bits_h, 1 << 30))
        sel = lambda a, b: jnp.where(
            use_h.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
        ac = sel(ac_h, ac)
        dcl = sel(dcl_h, dcl)
        recon = sel(recon_h, recon)
        mode = jnp.where(use_h, 1, 2).astype(jnp.int32)
    return ac, dcl, recon, mode, score


def _chroma_step(cmb, left_col, has_left, qp_c):
    """One MB column of one chroma plane across all rows.

    cmb: (R, 8, 8); left_col: (R, 8).  DC prediction per 4x4 quadrant: with
    the top slice boundary, quadrant (bx, by) predicts from left rows
    4*by..4*by+3 (spec §8.3.4.1 fallbacks), or 128 with no left MB.
    """
    lsum = left_col.reshape(-1, 2, 4).sum(axis=-1)    # (R, by)
    pq = (lsum + 2) >> 2                              # (R, by)
    pred_q = jnp.where(has_left, pq[:, :, None], 128)  # (R, by, bx)
    res = _blocks(cmb, 2) - pred_q[..., None, None]
    w = _fwd4x4(res)
    dc = w[..., 0, 0]                                 # (R, 2, 2)
    ac = quant.h264_quantize_4x4(w, qp_c, intra=True)
    ac = ac.at[..., 0, 0].set(0)
    wd = _had2(dc)
    dcl = quant.h264_quantize_chroma_dc(wd, qp_c)

    fd = _had2(dcl)
    dcc = quant.h264_dequantize_chroma_dc(fd, qp_c)
    wr = quant.h264_dequantize_4x4(ac, qp_c)
    wr = wr.at[..., 0, 0].set(dcc)
    resr = _inv4x4(wr)
    recon = jnp.clip(pred_q[..., None, None] + resr, 0, 255)
    return ac, dcl, _unblocks(recon)


# ---------------------------------------------------------------------------
# I_NxN (I4x4) luma path — per-4x4-block prediction under slice-per-row
#
# Coding structure chosen for the MB-column scan: the decoder's intra-4x4
# dependency graph inside an MB (left/top/top-right recon) collapses under
# slice-per-row into SEVEN sequential sub-steps per MB, each fully
# vectorized across frame rows:
#
#   - block row by=0 (top row of the slice: no samples above) -> four
#     sequential blocks along bx using the LEFT-family modes
#     {Horizontal, Horizontal-Up, DC(left-only)};
#   - block rows by=1..3 -> one step each, all four bx in parallel, using
#     the VERTICAL-family modes {Vertical, Diagonal-Down-Left,
#     Vertical-Left} whose reference samples come only from the row above
#     (top-right handled by the spec's p[3,-1] substitution where the
#     z-order neighbor is not yet decoded).
#
# Modes outside those sets are never *chosen* (an encoder decision, always
# legal); every emitted mode is computable by a conformant decoder from
# available samples only.  Every decision (block mode, I16 DC-vs-H, and
# the MB-level I16-vs-I4 choice) minimizes estimated CAVLC bits.
# ---------------------------------------------------------------------------

# TR availability per raster (by, bx), by >= 1: the above-right 4x4 block
# must precede the current one in luma4x4BlkIdx (z) coding order.
_BLKIDX_RASTER = np.zeros((4, 4), np.int32)          # [by][bx] -> blkIdx
for _i, (_bx, _by) in enumerate(LUMA_BLOCK_ORDER):
    _BLKIDX_RASTER[_by, _bx] = _i
_TR_AVAIL = np.zeros((4, 4), bool)
for _by in range(1, 4):
    for _bx in range(3):
        _TR_AVAIL[_by, _bx] = (_BLKIDX_RASTER[_by - 1, _bx + 1]
                               < _BLKIDX_RASTER[_by, _bx])
del _i, _bx, _by


def _level_bits_est(lv, axes):
    """Crude CAVLC bit estimate for quantized levels: ~3 bits per nonzero
    plus ~2 per extra magnitude bit.  Used only for the I16-vs-I4
    decision, which must compare *coded size* — a SAD comparison
    systematically overfits toward I4 on noise (sixteen best-of-three
    predictors always beat one, spuriously) while paying ~40+ signaling
    bits per MB for nothing."""
    a = jnp.abs(lv)
    nz = (a > 0).astype(jnp.int32)
    extra = jnp.floor(jnp.log2(jnp.maximum(a, 1).astype(jnp.float32)))
    return (3 * nz + 2 * extra.astype(jnp.int32)).sum(axis=axes)


def _i4_code_block(blk, preds, modes, legal, qp, lam=None):
    """Choose-among-candidates + transform/quant/recon for I4 blocks.

    blk: (..., 4, 4); preds: list of (..., 4, 4); legal: list of (...,)
    bool (or True).  Every candidate is fully coded and the cheapest one
    kept (same rationale as the I16 decision): by estimated CAVLC bits
    alone under tune=off (``lam is None``), by the Lagrangian
    SSD + lam * bits under tune=hq — which costs one extra
    dequant/idct/clip per candidate (the rest of the per-candidate work
    was already paid) and is the bulk of hq's extra device cycles.
    Returns (mode (...,), levels_zz (..., 16), recon (..., 4, 4),
    score (...,)).
    """
    if lam is not None:
        lam_b = jnp.asarray(lam, jnp.float32)
        lam_b = lam_b.reshape(lam_b.shape + (1,) * (blk.ndim - 2 - lam_b.ndim))
        cands = []
        for p, lg in zip(preds, legal):
            w = _fwd4x4(blk - p)
            lv = quant.h264_quantize_4x4(w, qp, intra=True)
            rec = jnp.clip(p + _inv4x4(quant.h264_dequantize_4x4(lv, qp)),
                           0, 255)
            c = (_ssd(rec, blk, (-2, -1)).astype(jnp.float32)
                 + lam_b * _level_bits_est(lv, (-2, -1)))
            if lg is not True:
                c = jnp.where(lg, c, jnp.inf)
            cands.append((lv, rec, c))
        c = jnp.stack([cd[2] for cd in cands])         # (K, ...)
        k = jnp.argmin(c, axis=0)
        score = jnp.min(c, axis=0)
        lv, rec = cands[0][0], cands[0][1]
        for i in range(1, len(cands)):
            m = (k == i)[..., None, None]
            lv = jnp.where(m, cands[i][0], lv)
            rec = jnp.where(m, cands[i][1], rec)
        mode = jnp.asarray(modes, jnp.int32)[k]
        lvz = lv.reshape(lv.shape[:-2] + (16,))[..., jnp.asarray(ZIGZAG4)]
        return mode, lvz, rec, score
    cands = []
    for p, lg in zip(preds, legal):
        w = _fwd4x4(blk - p)
        lv = quant.h264_quantize_4x4(w, qp, intra=True)  # FULL 4x4, no DC
        b = _level_bits_est(lv, (-2, -1))
        if lg is not True:
            b = jnp.where(lg, b, 1 << 30)
        cands.append((lv, p, b))
    b = jnp.stack([c[2] for c in cands])               # (K, ...)
    k = jnp.argmin(b, axis=0)
    bits = jnp.min(b, axis=0)
    lv, pred = cands[0][0], cands[0][1]
    for i in range(1, len(cands)):
        m = (k == i)[..., None, None]
        lv = jnp.where(m, cands[i][0], lv)
        pred = jnp.where(m, cands[i][1], pred)
    mode = jnp.asarray(modes, jnp.int32)[k]
    wr = quant.h264_dequantize_4x4(lv, qp)
    rec = jnp.clip(pred + _inv4x4(wr), 0, 255)
    lvz = lv.reshape(lv.shape[:-2] + (16,))[..., jnp.asarray(ZIGZAG4)]
    return mode, lvz, rec, bits


def _hu_pred(left):
    """Horizontal-Up (mode 8) from left samples L0..L3: (..., 4) -> 4x4."""
    l0, l1, l2, l3 = (left[..., i] for i in range(4))
    z = [(l0 + l1 + 1) >> 1,                 # zHU 0
         (l0 + 2 * l1 + l2 + 2) >> 2,        # 1
         (l1 + l2 + 1) >> 1,                 # 2
         (l1 + 2 * l2 + l3 + 2) >> 2,        # 3
         (l2 + l3 + 1) >> 1,                 # 4
         (l2 + 3 * l3 + 2) >> 2,             # 5
         l3, l3]                             # >= 6
    rows = [jnp.stack([z[min(x + 2 * y, 7)] for x in range(4)], axis=-1)
            for y in range(4)]
    return jnp.stack(rows, axis=-2)          # (..., 4, 4)


def _vert_preds(p8):
    """Vertical-family predictions from top samples p[0..7,-1]: (..., 8).

    Returns (V, DDL, VL), each (..., 4, 4)."""
    p = [p8[..., i] for i in range(8)]
    v = jnp.stack([jnp.stack([p[x] for x in range(4)], axis=-1)] * 4,
                  axis=-2)
    def ddl(y, x):
        i = x + y
        if i == 6:                                   # x == 3 and y == 3
            return (p[6] + 3 * p[7] + 2) >> 2
        return (p[i] + 2 * p[i + 1] + p[i + 2] + 2) >> 2
    ddl_m = jnp.stack([jnp.stack([ddl(y, x) for x in range(4)], axis=-1)
                       for y in range(4)], axis=-2)
    def vl(y, x):
        i = x + (y >> 1)
        if y % 2 == 0:
            return (p[i] + p[i + 1] + 1) >> 1
        return (p[i] + 2 * p[i + 1] + p[i + 2] + 2) >> 2
    vl_m = jnp.stack([jnp.stack([vl(y, x) for x in range(4)], axis=-1)
                      for y in range(4)], axis=-2)
    return v, ddl_m, vl_m


def _diag_preds(t8, l4, tl):
    """The three both-neighbor diagonal modes from top t8 (..., 8), left
    l4 (..., 4) and top-left tl (...,): (DDR, VR, HD), each (..., 4, 4)
    — spec 8.3.1.2.4-6."""
    t = [t8[..., i] for i in range(8)]
    l_ = [l4[..., i] for i in range(4)]

    def tt(i):                       # t with index -1 = top-left
        return tl if i < 0 else t[i]

    def ll(i):
        return tl if i < 0 else l_[i]

    def ddr(y, x):
        d = x - y
        if d > 0:
            return (tt(d - 2) + 2 * tt(d - 1) + tt(d) + 2) >> 2
        if d < 0:
            return (ll(-d - 2) + 2 * ll(-d - 1) + ll(-d) + 2) >> 2
        return (t[0] + 2 * tl + l_[0] + 2) >> 2

    def vr(y, x):
        z = 2 * x - y
        if z >= 0:
            i = x - (y >> 1)
            if z % 2 == 0:
                return (tt(i - 1) + tt(i) + 1) >> 1
            return (tt(i - 2) + 2 * tt(i - 1) + tt(i) + 2) >> 2
        if z == -1:
            return (l_[0] + 2 * tl + t[0] + 2) >> 2
        return (ll(y - 2 * x - 1) + 2 * ll(y - 2 * x - 2)
                + ll(y - 2 * x - 3) + 2) >> 2

    def hd(y, x):
        z = 2 * y - x
        if z >= 0:
            i = y - (x >> 1)
            if z % 2 == 0:
                return (ll(i - 1) + ll(i) + 1) >> 1
            return (ll(i - 2) + 2 * ll(i - 1) + ll(i) + 2) >> 2
        if z == -1:
            return (l_[0] + 2 * tl + t[0] + 2) >> 2
        return (tt(x - 2 * y - 1) + 2 * tt(x - 2 * y - 2)
                + tt(x - 2 * y - 3) + 2) >> 2

    def grid(f):
        return jnp.stack([jnp.stack([f(y, x) for x in range(4)], axis=-1)
                          for y in range(4)], axis=-2)

    return grid(ddr), grid(vr), grid(hd)


def _acc_score(total, score, lam):
    """Accumulate a block score into the MB total, clamping the illegal
    sentinel (int 1<<30 / float inf) so a sum cannot overflow/poison."""
    if lam is None:
        return total + jnp.minimum(score, 1 << 24)
    return total + jnp.minimum(score, jnp.float32(1e18))


def _i4_row0(ymb, left_col, has_left, qp, rec, raster_mode, raster_lvz,
             bits_total, lam=None):
    """Block row by=0 (top of the slice: no samples above): four
    bx-sequential blocks with the LEFT-family modes {H, HU, DC(left)}.
    Shared by the fast and full I4 paths."""
    nr = ymb.shape[0]
    for bx in range(4):
        blk = ymb[:, 0:4, bx * 4:bx * 4 + 4]
        if bx == 0:
            left4 = left_col[:, 0:4]
            avail = jnp.broadcast_to(has_left, (nr,))
        else:
            left4 = rec[:, 0:4, bx * 4 - 1]
            avail = jnp.ones((nr,), bool)
        pred_h = jnp.broadcast_to(left4[:, :, None], (nr, 4, 4))
        pred_hu = _hu_pred(left4)
        dc = jnp.where(avail, (left4.sum(axis=1) + 2) >> 2, 128)
        pred_dc = jnp.broadcast_to(dc[:, None, None], (nr, 4, 4))
        mode, lvz, rb, bits = _i4_code_block(
            blk, [pred_h, pred_hu, pred_dc], [1, 8, 2],
            [avail, avail, True], qp, lam=lam)
        rec = rec.at[:, 0:4, bx * 4:bx * 4 + 4].set(rb)
        raster_mode[(0, bx)] = mode
        raster_lvz[(0, bx)] = lvz
        bits_total = _acc_score(bits_total, bits, lam)
    return rec, bits_total


def _i4_stack(raster_mode, raster_lvz):
    """Raster dicts -> (levels (R, 16 blkIdx, 16), modes (R, 16 blkIdx))
    in luma4x4BlkIdx order."""
    modes = jnp.stack([raster_mode[(by, bx)]
                       for (bx, by) in LUMA_BLOCK_ORDER], axis=1)
    levels = jnp.stack([raster_lvz[(by, bx)]
                        for (bx, by) in LUMA_BLOCK_ORDER], axis=1)
    return levels, modes


def _i4_score0(nr, lam):
    return jnp.zeros((nr,), jnp.int32 if lam is None else jnp.float32)


def _luma_step_i4_full(ymb, left_col, has_left, qp, lam=None):
    """I4x4 with the FULL nine-mode set on block rows 1-3.

    Same contract as :func:`_luma_step_i4`.  The left-family and
    both-neighbor modes (H, HU, DDR, VR, HD, two-sided DC) make each
    block depend on its in-row left neighbor's reconstruction, so rows
    1-3 run bx-SEQUENTIALLY here (16 sub-steps per MB column vs 7) —
    measurably better compression for measurably more sequential depth;
    selected via i16_modes="full" (ENCODER_INTRA_MODES=full)."""
    nr = ymb.shape[0]
    rec = jnp.zeros_like(ymb)
    raster_mode = {}
    raster_lvz = {}
    bits_total = _i4_score0(nr, lam)
    rec, bits_total = _i4_row0(ymb, left_col, has_left, qp, rec,
                               raster_mode, raster_lvz, bits_total,
                               lam=lam)

    # block rows 1-3: all nine modes, sequential along bx
    for by in range(1, 4):
        y0 = by * 4
        for bx in range(4):
            blk = ymb[:, y0:y0 + 4, bx * 4:bx * 4 + 4]
            trow = rec[:, y0 - 1, bx * 4:bx * 4 + 4]            # (R, 4)
            if bx < 3 and _TR_AVAIL[by, bx]:
                tr = rec[:, y0 - 1, bx * 4 + 4:bx * 4 + 8]
            else:
                tr = jnp.broadcast_to(trow[:, 3:4], trow.shape)
            t8 = jnp.concatenate([trow, tr], axis=1)            # (R, 8)
            if bx == 0:
                l4 = left_col[:, y0:y0 + 4]
                tl = left_col[:, y0 - 1]
                avail = jnp.broadcast_to(has_left, (nr,))
            else:
                l4 = rec[:, y0:y0 + 4, bx * 4 - 1]
                tl = rec[:, y0 - 1, bx * 4 - 1]
                avail = jnp.ones((nr,), bool)
            v, ddl, vl = _vert_preds(t8)
            ddr, vr, hd = _diag_preds(t8, l4, tl)
            pred_h = jnp.broadcast_to(l4[:, :, None], (nr, 4, 4))
            pred_hu = _hu_pred(l4)
            # DC: both-available averages top+left; top-only otherwise
            # (the decoder applies the same availability rule, 8.3.1.2.3)
            dc_both = (t8[:, :4].sum(axis=1) + l4.sum(axis=1) + 4) >> 3
            dc_top = (t8[:, :4].sum(axis=1) + 2) >> 2
            dc = jnp.where(avail, dc_both, dc_top)
            pred_dc = jnp.broadcast_to(dc[:, None, None], (nr, 4, 4))
            mode, lvz, rb, bits = _i4_code_block(
                blk,
                [v, ddl, vl, pred_dc, pred_h, pred_hu, ddr, vr, hd],
                [0, 3, 7, 2, 1, 8, 4, 5, 6],
                [True, True, True, True, avail, avail, avail, avail,
                 avail], qp, lam=lam)
            rec = rec.at[:, y0:y0 + 4, bx * 4:bx * 4 + 4].set(rb)
            raster_mode[(by, bx)] = mode
            raster_lvz[(by, bx)] = lvz
            bits_total = _acc_score(bits_total, bits, lam)

    levels, modes = _i4_stack(raster_mode, raster_lvz)
    return levels, modes, rec, bits_total


def _luma_step_i4(ymb, left_col, has_left, qp, lam=None):
    """I4x4 candidate for one MB column across all rows.

    ymb: (R, 16, 16) int32; left_col: (R, 16).  Returns
    (levels (R, 16 blkIdx, 16 zigzag), modes (R, 16 blkIdx),
    recon (R, 16, 16), score (R,) — estimated bits, or SSD + lam * bits
    under tune=hq)."""
    nr = ymb.shape[0]
    rec = jnp.zeros_like(ymb)
    raster_mode = {}
    raster_lvz = {}
    bits_total = _i4_score0(nr, lam)
    rec, bits_total = _i4_row0(ymb, left_col, has_left, qp, rec,
                               raster_mode, raster_lvz, bits_total,
                               lam=lam)

    # --- block rows by=1..3: all bx parallel, vertical-family modes ----
    for by in range(1, 4):
        blks = ymb[:, by * 4:by * 4 + 4, :]
        blks = blks.reshape(nr, 4, 4, 4).transpose(0, 2, 1, 3)  # (R,bx,y,x)
        trow = rec[:, by * 4 - 1, :].reshape(nr, 4, 4)          # (R,bx,4)
        # p[4..7,-1]: above-right block's bottom row when its z-order
        # predecessor status allows, else the spec's p[3,-1] substitution
        tr = jnp.concatenate([trow[:, 1:], trow[:, 3:, :]], axis=1)
        sub = jnp.broadcast_to(trow[:, :, 3:4], trow.shape)
        avail_tr = jnp.asarray(_TR_AVAIL[by])[None, :, None]    # (1,bx,1)
        tr = jnp.where(avail_tr, tr, sub)
        p8 = jnp.concatenate([trow, tr], axis=2)                # (R,bx,8)
        v, ddl, vl = _vert_preds(p8)
        mode, lvz, rb, bits = _i4_code_block(
            blks, [v, ddl, vl], [0, 3, 7], [True, True, True], qp,
            lam=lam)
        rb = rb.transpose(0, 2, 1, 3).reshape(nr, 4, 16)
        rec = rec.at[:, by * 4:by * 4 + 4, :].set(rb)
        for bx in range(4):
            raster_mode[(by, bx)] = mode[:, bx]
            raster_lvz[(by, bx)] = lvz[:, bx]
        bits_total = bits_total + bits.sum(axis=1)

    levels, modes = _i4_stack(raster_mode, raster_lvz)
    return levels, modes, rec, bits_total


@functools.partial(jax.jit,
                   static_argnames=("pad_h", "pad_w", "qp", "i16_modes",
                                    "tune"))
def encode_intra_frame(rgb, pad_h: int, pad_w: int, qp: int,
                       i16_modes: str = "auto", tune: str = "off",
                       next_y=None):
    """Full device stage: RGB frame -> quantized level tensors + recon.

    Returns a dict of int32/uint8 arrays (see keys below); shapes use
    R = pad_h//16 MB rows and C = pad_w//16 MB columns.
    """
    h, w = rgb.shape[0], rgb.shape[1]
    with jax.named_scope("dngd.colour"):
        rgb_p = jnp.pad(jnp.asarray(rgb),
                        ((0, pad_h - h), (0, pad_w - w), (0, 0)),
                        mode="edge")
        yf, cbf, crf = color.rgb_to_yuv420(rgb_p, matrix="video")
        y = jnp.clip(jnp.round(yf), 0, 255).astype(jnp.int32)
        cb = jnp.clip(jnp.round(cbf), 0, 255).astype(jnp.int32)
        cr = jnp.clip(jnp.round(crf), 0, 255).astype(jnp.int32)
    return encode_intra_frame_yuv.__wrapped__(y, cb, cr, qp, i16_modes,
                                              tune, next_y)


@functools.partial(jax.jit, static_argnames=("qp", "i16_modes", "tune"))
@jax.named_scope("dngd.intra")
def encode_intra_frame_yuv(y, cb, cr, qp: int, i16_modes: str = "auto",
                           tune: str = "off", next_y=None):
    """Same device stage from pre-converted YUV 4:2:0 planes (already padded
    to macroblock multiples).  The host-side capture path converts RGB with
    cv2 (BT.601 studio range, matching ops/color "video") and ships 1.5
    bytes/pixel instead of 3 — the host->device link is the hot-path
    bottleneck (SURVEY.md §3.2 PCIe budget).

    ``i16_modes``: "auto" = per-MB choice among I16 DC/H and the I4x4
    path (fast mode sets); "full" = same but I4x4 block rows 1-3 search
    all NINE prediction modes (bx-sequential; ~2x the intra sequential
    depth for measurably fewer bits); "i16" = I16 DC/H only; "dc" = I16
    DC only (no deployment sets it: a test's flattest mode set).

    I16x16 Vertical and Plane are NOT mode-set gaps: under slice-per-MB-
    row the macroblock above is always in a different slice, and samples
    outside the slice are unavailable for intra prediction (spec 6.4.9 /
    8.3.3) — DC and Horizontal are the only LEGAL I16 modes in this
    geometry, for this encoder and for NVENC alike.

    ``tune`` (ENCODER_TUNE): "off" keeps every decision and output
    byte-identical to the pre-tune encoder.  "hq" adds (a) per-MB
    adaptive quantization — a qp plane from luma activity (ops/aq),
    plus a 1-frame lookahead bias when ``next_y`` is staged — and (b)
    Lagrangian D + lambda(qp) * R mode decisions for every intra
    choice.  "hq_noaq" keeps the lambda decisions but pins the qp plane
    flat (the deblock-enabled variant: the loop filter's thresholds are
    compiled per-slice-qp, so per-MB qp is v1-limited to deblock-off)."""
    y = jnp.asarray(y).astype(jnp.int32)
    cb = jnp.asarray(cb).astype(jnp.int32)
    cr = jnp.asarray(cr).astype(jnp.int32)
    if tune not in ("off", "hq", "hq_noaq"):
        raise ValueError(f"unknown tune {tune!r}")
    quant.require_static_qp_for(qp, tune)
    pad_h, pad_w = y.shape
    nr, nc = pad_h // 16, pad_w // 16
    allow_i4 = i16_modes in ("auto", "full")
    i4_step = _luma_step_i4_full if i16_modes == "full" else _luma_step_i4
    # I4's extra signaling vs I16: 16 mode elements (~1-4 b) + cbp ue
    # against the I16 combined mb_type — ~44 bits on the bit-estimate
    # scale of _level_bits_est.
    i4_sig_bits = 44

    qp_map = None
    if tune == "hq":
        from . import aq
        with jax.named_scope("dngd.aq"):
            qp_map = aq.qp_plane(y, qp, next_y)         # (R, C) absolute
        qpmbs = jnp.moveaxis(qp_map, 0, 1)              # (C, R) scan axis
        qcmbs = jnp.moveaxis(quant.chroma_qp_v(qp_map), 0, 1)
    else:
        qp_c = quant.chroma_qp_any(qp)

    # (C, R, ...) layouts: scan axis leading.
    ymbs = jnp.moveaxis(
        y.reshape(nr, 16, nc, 16).transpose(0, 2, 1, 3), 1, 0)
    cbmbs = jnp.moveaxis(
        cb.reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3), 1, 0)
    crmbs = jnp.moveaxis(
        cr.reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3), 1, 0)

    def step(carry, xs):
        yl, cbl, crl = carry
        if tune == "hq":
            ymb, cbmb, crmb, idx, qp_s, qc_s = xs
            lam = None
            from . import aq
            lam = aq.lam_mode(qp_s)                     # (R,) float32
        else:
            ymb, cbmb, crmb, idx = xs
            qp_s, qc_s = qp, qp_c
            lam = None
            if tune == "hq_noaq":
                from . import aq
                lam = float(aq.lam_mode(qp))
        has_left = idx > 0
        y_ac, y_dc, y_rec, y_mode, bits16 = _luma_step(
            ymb, yl, has_left, qp_s, allow_h=i16_modes != "dc", lam=lam)
        if allow_i4:
            lv4, modes4, rec4, bits4 = i4_step(ymb, yl, has_left, qp_s,
                                               lam=lam)
            if lam is None:
                use4 = bits4 + i4_sig_bits < bits16         # (R,)
            else:
                use4 = bits4 + lam * i4_sig_bits < bits16
            y_rec = jnp.where(use4[:, None, None], rec4, y_rec)
        else:
            lv4 = jnp.zeros((ymb.shape[0], 16, 16), jnp.int32)
            modes4 = jnp.full((ymb.shape[0], 16), 2, jnp.int32)
            use4 = jnp.zeros((ymb.shape[0],), bool)
        cb_ac, cb_dc, cb_rec = _chroma_step(cbmb, cbl, has_left, qc_s)
        cr_ac, cr_dc, cr_rec = _chroma_step(crmb, crl, has_left, qc_s)
        carry = (y_rec[:, :, 15], cb_rec[:, :, 7], cr_rec[:, :, 7])
        out = (y_ac, y_dc, cb_ac, cb_dc, cr_ac, cr_dc,
               y_rec.astype(jnp.uint8), cb_rec.astype(jnp.uint8),
               cr_rec.astype(jnp.uint8), y_mode, lv4, modes4, use4)
        return carry, out

    init = (jnp.zeros((nr, 16), jnp.int32), jnp.zeros((nr, 8), jnp.int32),
            jnp.zeros((nr, 8), jnp.int32))
    xs = (ymbs, cbmbs, crmbs, jnp.arange(nc, dtype=jnp.int32))
    if tune == "hq":
        xs = xs + (qpmbs, qcmbs)
    _, outs = jax.lax.scan(step, init, xs)
    (y_ac, y_dc, cb_ac, cb_dc, cr_ac, cr_dc, y_rec, cb_rec, cr_rec,
     y_mode, y_lv4, y_modes4, y_use4) = outs
    # scan stacked along axis 0 = columns; put rows first: (R, C, ...)
    to_rc = lambda a: jnp.moveaxis(a, 0, 1)

    # --- scan-order reordering (device-side gathers) ---
    zz = jnp.asarray(ZIGZAG4)
    blk = jnp.asarray(LUMA_BLOCK_ORDER)

    y_ac = to_rc(y_ac)                                 # (R, C, by, bx, 4, 4)
    y_acf = y_ac.reshape(nr, nc, 4, 4, 16)[..., zz[1:]]  # zigzag, AC only
    # gather blocks into luma4x4BlkIdx order: index [by, bx] per blkIdx
    y_acf = y_acf[:, :, blk[:, 1], blk[:, 0], :]       # (R, C, 16, 15)

    y_dcf = to_rc(y_dc).reshape(nr, nc, 16)[..., zz]   # (R, C, 16)

    def chroma_fmt(ac, dc):
        ac = to_rc(ac).reshape(nr, nc, 4, 16)[..., zz[1:]]  # blocks raster
        dc = to_rc(dc).reshape(nr, nc, 4)
        return ac, dc

    cb_acf, cb_dcf = chroma_fmt(cb_ac, cb_dc)
    cr_acf, cr_dcf = chroma_fmt(cr_ac, cr_dc)

    # recon planes reassembled for tests / PSNR
    y_full = to_rc(y_rec).transpose(0, 2, 1, 3).reshape(pad_h, pad_w)
    cb_full = to_rc(cb_rec).transpose(0, 2, 1, 3).reshape(pad_h // 2, pad_w // 2)
    cr_full = to_rc(cr_rec).transpose(0, 2, 1, 3).reshape(pad_h // 2, pad_w // 2)

    out = {
        "luma_dc": y_dcf,        # (R, C, 16) zigzag
        "luma_ac": y_acf,        # (R, C, 16 blkIdx, 15) zigzag
        "cb_dc": cb_dcf,         # (R, C, 4) raster
        "cb_ac": cb_acf,         # (R, C, 4 raster, 15)
        "cr_dc": cr_dcf,
        "cr_ac": cr_acf,
        "pred_mode": to_rc(y_mode),   # (R, C) Intra16x16PredMode (1=H, 2=DC)
        "mb_i4": to_rc(y_use4),       # (R, C) MB coded I_NxN
        "i4_modes": to_rc(y_modes4),  # (R, C, 16 blkIdx) Intra4x4PredMode
        "luma_i4": to_rc(y_lv4),      # (R, C, 16 blkIdx, 16) zigzag levels
        "recon_y": y_full, "recon_cb": cb_full, "recon_cr": cr_full,
    }
    if qp_map is not None:
        out["qp_map"] = qp_map        # (R, C) absolute per-MB qp (tune=hq)
    return out


#: qp-traced twin (tune="off" only), for the per-frame CABAC path — see
#: cavlc_device.encode_intra_cavlc_frame_yuv_dynqp.
encode_intra_frame_yuv_dynqp = jax.jit(
    encode_intra_frame_yuv.__wrapped__, static_argnames=("i16_modes", "tune"))
