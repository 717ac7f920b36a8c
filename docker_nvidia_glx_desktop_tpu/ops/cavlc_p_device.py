"""Device-side CAVLC entropy for P-frames.

Companion to :mod:`.cavlc_device` (the intra entropy stage): the same
frame pack (:func:`.cavlc_device.pack_frame`), with the P-slice MB layer
built on device instead of a fixed syntax table:

- **mb_skip_run**: with slice-per-row, a skipped MB is exactly
  ``mv == (0,0) and cbp == 0``; each coded MB's preceding run is a
  row-local cummax over coded positions, and a per-row trailing-run slot
  covers slices that end in skips — all dense ops, no sequencing.
- **mvd**: mvp is the left MB's MV (spec §8.4.1.3 with B/C in other
  slices), so mvd is one shift + subtract over the MV field; signed
  Exp-Golomb lengths come from a bit-length gather table.
- **residual blocks**: 26 per MB (16 luma 16-coef blocks — inter MBs have
  no luma DC Hadamard — 2 chroma DC, 8 chroma AC), gated by the inter
  CBP (per-8x8-group luma bits, Table 9-4 inter codeNum mapping).

The host pulls the same flat metadata+bitstream buffer as the intra path
(one bucketed transfer per frame, ~100x smaller than the level tensors the
host-entropy P path pulls), and the reconstruction planes never leave the
device — they are the next frame's reference.

Byte-identity contract with the Python reference
(:func:`..bitstream.h264_entropy.encode_p_picture`) is enforced in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..bitstream.h264_entropy import _CBP_INTER_BY_CODENUM
from .cavlc_device import blocks_first, code_blocks, nc_grid, pack_frame
from .h264_inter import RING_DONATE
# The tunes whose per-frame step takes the slice qp as a TRACED scalar: the
# program's own word on it, which the benchmark's hq readers hold it to
# (benchmark/layer_metrics/_hq.py).
from .quant import TRACED_QP_TUNES as DYNQP_STEP_TUNES  # noqa: F401

_I32 = np.int32

P_MB_BLOCKS = 26          # 16 luma + 2 chroma DC + 8 chroma AC
P_MB_BLOCKS_I = 27        # + Intra16x16DCLevel (tune=hq I16-in-P path)
HDR_SLOT_COUNT = 7        # skip_run, mb_type, mvd_x, mvd_y, cbp,
                          # intra_chroma_pred_mode, qp_delta (per-slot
                          # zero lengths collapse: an inter MB emits no
                          # chroma-mode bits, an intra MB no mvd/cbp)

# bit_length(v) for v in [0, 2048): the largest ue argument is a fully
# skipped row's trailing run (code = row_width_in_MBs + 1, so 2048 covers
# widths beyond 32K px) plus every mvd/cbp codeword.
_BITLEN = np.zeros(2048, _I32)
for _v in range(1, 2048):
    _BITLEN[_v] = _v.bit_length()

# cbp value (0..47) -> inter codeNum (Table 9-4)
_CBP_TO_CODENUM = np.zeros(48, _I32)
for _cn, _cbp in enumerate(_CBP_INTER_BY_CODENUM):
    _CBP_TO_CODENUM[_cbp] = _cn
del _cn, _cbp, _v


def _ue(v):
    """Unsigned Exp-Golomb as (value, length) slot arrays (v < 2047)."""
    code = v + 1
    n = jnp.asarray(_BITLEN)[code]
    return code.astype(jnp.uint32), 2 * n - 1


def _se(v):
    """Signed Exp-Golomb as (value, length)."""
    code = jnp.where(v > 0, 2 * v - 1, -2 * v)
    return _ue(code)


def p_mb_header_slots(mv, cbp, qp_se=None, mb_intra=None):
    """Per-MB P-slice header slots + per-row trailing skip run.

    mv: (R, C, 2) quarter-pel; cbp: (R, C) coded_block_pattern.
    Returns (vals (R,C,7) uint32, lens (R,C,7) int32 — all-zero lens for
    skipped MBs, trail_vals (R,) uint32, trail_lens (R,)).

    ``qp_se`` (tune=hq): per-MB (value, length) override for the
    mb_qp_delta slot, lengths pre-gated to the MBs whose syntax carries
    it (cbp != 0, or I_16x16 which always codes it).

    ``mb_intra`` (tune=hq I16-in-P): (R, C) bool — MBs coded I_16x16/DC
    inside the P slice.  For those, ``cbp`` carries the INTRA pattern
    (luma 0/15 + 16 * chroma): mb_type = 5 + (1 + 2 + 4 * cbp_chroma +
    12 * [cbp_luma != 0]) per Table 7-11 with predMode DC, the mvd and
    coded_block_pattern slots are absent (I16 cbp rides in mb_type), and
    intra_chroma_pred_mode DC is one ue(0) bit.  An intra MB is never
    skipped, and its (0, 0) entry in ``mv`` is exactly the zero vector
    the spec substitutes for an intra neighbor in mv prediction, so the
    plain left-shift mvp below stays normative.
    """
    nr, nc = cbp.shape
    intra = (jnp.zeros((nr, nc), bool) if mb_intra is None
             else jnp.asarray(mb_intra, bool))
    zero_mv = jnp.all(mv == 0, axis=-1)
    skip = zero_mv & (cbp == 0) & ~intra
    coded = ~skip

    idx = jnp.arange(nc, dtype=jnp.int32)[None, :]
    # index of the most recent coded MB at or before each position
    coded_idx = jnp.where(coded, idx, -1)
    prev_inclusive = jax.lax.cummax(coded_idx, axis=1)
    # previous coded STRICTLY before: shift right with -1 fill
    prev_excl = jnp.concatenate(
        [jnp.full((nr, 1), -1, jnp.int32), prev_inclusive[:, :-1]], axis=1)
    run = idx - prev_excl - 1                          # (R, C)

    # mvp = left MB's mv (skipped MBs carry (0,0) which is their derived
    # motion, so a plain shift is exact); first column predicts from 0.
    mvp = jnp.concatenate(
        [jnp.zeros((nr, 1, 2), mv.dtype), mv[:, :-1]], axis=1)
    mvd = (mv - mvp).astype(jnp.int32)

    v_run, l_run = _ue(run)
    # mb_type: P_L0_16x16 = ue(0); I_16x16 in a P slice = ue(5 + intra
    # table index), predMode DC (2) with the I16 cbp folded in
    t_intra = 8 + 4 * (cbp >> 4) + jnp.where((cbp & 15) > 0, 12, 0)
    v_type, l_type = _ue(jnp.where(intra, t_intra, 0))
    v_mx, l_mx = _se(mvd[..., 1])                      # quarter-pel x
    v_my, l_my = _se(mvd[..., 0])                      # quarter-pel y
    v_cbp, l_cbp = _ue(jnp.asarray(_CBP_TO_CODENUM)[
        jnp.where(intra, 0, cbp)])
    not_i = ~intra
    l_mx = l_mx * not_i
    l_my = l_my * not_i
    l_cbp = l_cbp * not_i
    # intra_chroma_pred_mode: DC = ue(0), intra MBs only
    v_icp = jnp.ones_like(run, jnp.uint32)
    l_icp = jnp.where(intra, 1, 0)
    if qp_se is None:
        v_qpd, l_qpd = _se(jnp.zeros_like(run))
        # qp_delta iff cbp != 0, or always for I_16x16
        l_qpd = jnp.where((cbp > 0) | intra, l_qpd, 0)
    else:
        v_qpd, l_qpd = qp_se                           # tune=hq chain

    vals = jnp.stack([v_run, v_type, v_mx, v_my, v_cbp, v_icp, v_qpd],
                     axis=-1)
    lens = jnp.stack([l_run, l_type, l_mx, l_my, l_cbp, l_icp, l_qpd],
                     axis=-1)
    lens = lens * coded[:, :, None]                    # skip MBs emit nothing

    # trailing skip run: MBs after the last coded one (possibly the whole
    # row); length 0 when the row ends on a coded MB.
    last_coded = prev_inclusive[:, -1]                 # (R,)
    trail = nc - 1 - last_coded
    tv, tl = _ue(trail)
    trail_lens = jnp.where(trail > 0, tl, 0)
    return vals, lens, tv, trail_lens, skip


def p_frame_block_slots(out: dict):
    """Inter residual tensors (ops/h264_inter.encode_p_frame) -> block
    slots + gates.  Returns (values, lengths, cbp, mv) with values/lengths
    (26, R * C, 34), block-major as ``cavlc_device.pack_frame`` takes them
    — or (27, R * C, 34) when the tune=hq I16-in-P path is
    active (``mb_intra`` in ``out``): block 0 is then Intra16x16DCLevel
    (gated to intra MBs; always coded there) and the 16 luma slots carry
    15-coefficient AC blocks for intra MBs (max_coeff 15 — total_zeros is
    absent when total_coeff reaches it) while inter MBs keep their
    16-coefficient LumaLevel4x4 blocks.  ``cbp`` for an intra MB is the
    INTRA pattern (0/15 luma + 16 * chroma) the mb_type table folds in."""
    mb_intra = out.get("mb_intra")
    mv = out["mv"].astype(jnp.int32)
    luma = out["luma"].astype(jnp.int32)               # (R, C, 16, 16)
    cb_dc = out["cb_dc"].astype(jnp.int32)
    cb_ac = out["cb_ac"].astype(jnp.int32)
    cr_dc = out["cr_dc"].astype(jnp.int32)
    cr_ac = out["cr_ac"].astype(jnp.int32)
    nr, nc_mb = luma.shape[:2]

    # --- inter CBP: luma bit per 8x8 group, chroma 2 levels -------------
    luma_grp_any = jnp.any(
        luma.reshape(nr, nc_mb, 4, 4, 16) != 0, axis=(3, 4))   # (R,C,4)
    cbp_luma = (luma_grp_any
                * (1 << jnp.arange(4, dtype=jnp.int32))).sum(axis=2)
    chroma_ac_any = (jnp.any(cb_ac != 0, axis=(2, 3))
                     | jnp.any(cr_ac != 0, axis=(2, 3)))
    chroma_dc_any = (jnp.any(cb_dc != 0, axis=2)
                     | jnp.any(cr_dc != 0, axis=2))
    cbp_chroma = jnp.where(chroma_ac_any, 2,
                           jnp.where(chroma_dc_any, 1, 0))
    cbp = cbp_luma + 16 * cbp_chroma                   # (R, C)

    # --- per-4x4 total_coeff (gated by the group bit), nC grids ---------
    from .cavlc_device import _BLK_X, _BLK_Y

    grp_gate = luma_grp_any[:, :, jnp.arange(16) // 4]         # (R,C,16)
    tc_blk = jnp.count_nonzero(luma, axis=3).astype(jnp.int32) * grp_gate
    if mb_intra is not None:
        intra = jnp.asarray(mb_intra, bool)
        i16_dc = out["i16_dc"].astype(jnp.int32)       # (R, C, 16)
        i16_ac = out["i16_ac"].astype(jnp.int32)       # (R, C, 16, 15)
        cl15 = jnp.any(i16_ac != 0, axis=(2, 3))       # (R, C)
        # the header's cbp: intra pattern for intra MBs (device zeroes
        # the inter luma there, so cbp_luma is already 0)
        cbp = jnp.where(intra, jnp.where(cl15, 15, 0) + 16 * cbp_chroma,
                        cbp)
        # neighbor total_coeff contexts: an intra MB's 4x4 counts come
        # from its (gated) AC block
        tc_i = (jnp.count_nonzero(i16_ac, axis=3).astype(jnp.int32)
                * cl15[:, :, None])
        tc_blk = jnp.where(intra[:, :, None], tc_i, tc_blk)
    tc_luma = jnp.zeros((nr, nc_mb, 4, 4), jnp.int32)
    tc_luma = tc_luma.at[:, :, jnp.asarray(_BLK_Y),
                         jnp.asarray(_BLK_X)].set(tc_blk)

    def chroma_tc(ac):
        t = jnp.count_nonzero(ac, axis=3).astype(jnp.int32)
        t = t * (cbp_chroma == 2)[:, :, None]
        return t.reshape(nr, nc_mb, 2, 2)

    tc_cb, tc_cr = chroma_tc(cb_ac), chroma_tc(cr_ac)
    ncl = nc_grid(tc_luma, tc_luma[:, :, :, 3])
    nccb = nc_grid(tc_cb, tc_cb[:, :, :, 1])
    nccr = nc_grid(tc_cr, tc_cr[:, :, :, 1])

    nmb = nr * nc_mb
    nblk = P_MB_BLOCKS if mb_intra is None else P_MB_BLOCKS_I

    def pad16(a):
        k = a.shape[-1]
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 16 - k)])

    # The blocks are numbered block-major, index = block * macroblocks +
    # macroblock (cavlc_device.frame_block_slots): the long macroblock
    # axis is what the chip's lanes run along, from here to the packer.
    luma_eff = luma
    if mb_intra is not None:
        luma_eff = jnp.where(intra[:, :, None, None],
                             pad16(i16_ac), luma)
    parts = [
        blocks_first(luma_eff),                        # 16 luma blocks
        pad16(cb_dc).reshape(1, nmb, 16),
        pad16(cr_dc).reshape(1, nmb, 16),
        blocks_first(pad16(cb_ac)),
        blocks_first(pad16(cr_ac))]
    if mb_intra is not None:
        parts.insert(0, i16_dc.reshape(1, nmb, 16))    # Intra16x16DCLevel
    blk_levels = jnp.concatenate(parts, axis=0)        # (nblk, R*C, 16)

    nc_luma_blk = ncl[:, :, jnp.asarray(_BLK_Y), jnp.asarray(_BLK_X)]
    nc_c = lambda g: blocks_first(g.reshape(nr, nc_mb, 4))
    nc_parts = [
        blocks_first(nc_luma_blk),
        jnp.zeros((2, nmb), jnp.int32),                # chroma DC: nC=-1
        nc_c(nccb), nc_c(nccr)]
    if mb_intra is not None:
        # Intra16x16DCLevel derives nC exactly as luma4x4BlkIdx 0
        nc_parts.insert(0, ncl[:, :, 0, 0].reshape(1, nmb))
    blk_nc = jnp.concatenate(nc_parts, axis=0)         # (nblk, R*C)

    off = 0 if mb_intra is None else 1
    is_cdc = np.zeros(nblk, bool)
    is_cdc[off + 16] = is_cdc[off + 17] = True
    max_coeff = np.full(nblk, 15, _I32)
    max_coeff[off:off + 16] = 16
    max_coeff[off + 16] = max_coeff[off + 17] = 4
    if mb_intra is None:
        mc = jnp.asarray(np.repeat(max_coeff, nmb))
    else:
        max_coeff[0] = 16                              # Intra16x16DCLevel
        # intra luma AC blocks are 15-coefficient (total_zeros absent
        # when total_coeff == 15, unlike the 16-coef inter blocks)
        luma_blk = np.zeros(nblk, bool)
        luma_blk[off:off + 16] = True
        mc = jnp.where(intra.reshape(1, nmb) & luma_blk[:, None],
                       15, max_coeff[:, None]).reshape(-1)

    values, lengths = code_blocks(
        blk_levels.reshape(nblk * nmb, 16),
        blk_nc.reshape(-1),
        jnp.asarray(np.repeat(is_cdc, nmb)),
        mc)
    values = values.reshape(nblk, nmb, -1)
    lengths = lengths.reshape(nblk, nmb, -1)

    chroma_gate = jnp.stack([cbp_chroma > 0] * 2 + [cbp_chroma == 2] * 8)
    if mb_intra is None:
        luma_gate = [blocks_first(grp_gate)]
    else:
        luma_gate = [intra.reshape(1, nmb),            # DC: intra only
                     blocks_first(jnp.where(intra[:, :, None],
                                            cl15[:, :, None], grp_gate))]
    gate = jnp.concatenate(
        luma_gate + [chroma_gate.reshape(10, nmb)], axis=0)    # (nblk, R*C)
    lengths = lengths * gate[:, :, None]
    return values, lengths, cbp, mv


@functools.partial(jax.jit, static_argnames=("qp", "tune", "p_intra",
                                             "with_qp_eff"),
                   donate_argnames=RING_DONATE)
def encode_p_cavlc_frame(y, cb, cr, ref_y, ref_cb, ref_cr,
                         hdr_vals, hdr_lens, qp: int, tune: str = "off",
                         next_y=None, p_intra: bool = False,
                         with_qp_eff: bool = False):
    """Fused P-frame device stage: ME/MC/residual (ops/h264_inter) +
    device CAVLC.  Returns (flat, recon_y, recon_cb, recon_cr, mv, nnz,
    levels) — only ``flat``'s prefix crosses the host link; the recon
    stays on device as the next reference, written IN PLACE of the
    donated refs (recon shapes/dtypes match exactly, so XLA aliases the
    buffers — the ring-buffer contract of ROADMAP item 2; callers must
    treat the passed refs as consumed).  ``levels`` carries the residual
    tensors the host entropy coder would need, so a flat-cap overflow
    falls back to host CAVLC of the SAME levels without ever re-reading
    the (now dead) reference planes — the levels are lazy device arrays
    and cross the link only on that rare path.  ``with_qp_eff`` (tune=hq
    with the loop filter on): ``levels["qp_eff"]`` is the (R, C) plane of
    effective qps the filter's thresholds follow."""
    from . import h264_inter

    out = h264_inter.encode_p_frame.__wrapped__(
        y, cb, cr, ref_y, ref_cb, ref_cr, qp, tune, next_y, p_intra)
    return _finish_p(out, hdr_vals, hdr_lens, slice_qp=qp,
                     with_qp_eff=with_qp_eff)


#: qp-traced twin (:data:`DYNQP_STEP_TUNES`) — see
#: cavlc_device.encode_intra_cavlc_frame_yuv_dynqp.
encode_p_cavlc_frame_dynqp = jax.jit(
    encode_p_cavlc_frame.__wrapped__,
    static_argnames=("tune", "p_intra", "with_qp_eff"),
    donate_argnames=RING_DONATE)


def encode_p_cavlc_frame_padded(y, cb, cr, ref_y_pad, ref_cb_pad,
                                ref_cr_pad, hdr_vals, hdr_lens, qp: int,
                                tune: str = "off", next_y=None,
                                p_intra: bool = False):
    """P stage from ``_PAD``-padded references — the spatially-sharded
    batch path's entry, where the padding rows are neighbor-shard halos
    instead of edge replication (parallel/batch.py).  Same 7-tuple
    return as :func:`encode_p_cavlc_frame` (shard callers drop the
    trailing ``levels`` before the collective gathers)."""
    from . import h264_inter

    out = h264_inter.encode_p_frame_padded_ref(
        y, cb, cr, ref_y_pad, ref_cb_pad, ref_cr_pad, qp, tune=tune,
        next_y=next_y, p_intra=p_intra)
    return _finish_p(out, hdr_vals, hdr_lens, slice_qp=qp)


def _finish_p(out: dict, hdr_vals, hdr_lens, slice_qp: int = None,
              with_qp_eff: bool = False):
    import jax.numpy as jnp
    import numpy as np

    from .h264_device import LUMA_BLOCK_ORDER

    with jax.named_scope("dngd.slots"):
        values, lengths, cbp, mv = p_frame_block_slots(out)
        mb_intra = out.get("mb_intra")
        qp_se = None
        qp_sum = eff = None
        if "qp_map" in out:
            from . import aq
            with jax.named_scope("dngd.aq"):
                codes = cbp > 0        # skip MBs have cbp == 0 too
                if mb_intra is not None:   # I_16x16 always codes mb_qp_delta
                    codes = codes | jnp.asarray(mb_intra, bool)
                eff, delta = aq.qp_chain(out["qp_map"], codes, slice_qp)
            from .cavlc_device import se_slots
            sv, sl = se_slots(delta)
            qp_se = (sv, jnp.where(codes, sl, 0))
            qp_sum = jnp.sum(eff).astype(jnp.uint32)
        hv6, hl6, tv, tl, _skip = p_mb_header_slots(mv, cbp, qp_se=qp_se,
                                                    mb_intra=mb_intra)
    with jax.named_scope("dngd.pack"):
        flat, _ = pack_frame(
            values, lengths, hv6, hl6, hdr_vals, hdr_lens, tv, tl,
            qp_sum=qp_sum,
            p_intra_mbs=None if mb_intra is None else jnp.sum(mb_intra))
    with jax.named_scope("dngd.deblock_bs"):
        # per-4x4 coded-coefficient flags in raster [by][bx] order — the
        # deblocking bS=2 input (ops/h264_deblock.p_bs)
        luma = out["luma"]                              # (R,C,16blk,16)
        nnz_idx = jnp.any(luma != 0, axis=-1)           # blkIdx order
        nr, nc = nnz_idx.shape[:2]
        nnz = jnp.zeros((nr, nc, 4, 4), bool)
        nnz = nnz.at[:, :, np.asarray(LUMA_BLOCK_ORDER[:, 1]),
                     np.asarray(LUMA_BLOCK_ORDER[:, 0])].set(nnz_idx)
    # residual levels for the host-entropy overflow fallback (mv rides
    # separately); pulled only when the flat cap overflowed.  The
    # tune=hq qp plane rides along: the fallback must re-emit the SAME
    # per-MB deltas the levels were quantized under.
    levels = {k: out[k] for k in ("luma", "cb_dc", "cb_ac",
                                  "cr_dc", "cr_ac")}
    if "qp_map" in out:
        levels["qp_map"] = out["qp_map"]
        if with_qp_eff:
            levels["qp_eff"] = eff
    if mb_intra is not None:       # I16-in-P tensors for the same fallback
        for k in ("mb_intra", "i16_dc", "i16_ac"):
            levels[k] = out[k]
    return (flat, out["recon_y"], out["recon_cb"], out["recon_cr"],
            out["mv"], nnz, levels)
