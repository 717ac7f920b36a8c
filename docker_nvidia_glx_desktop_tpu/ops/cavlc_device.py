"""Device-side CAVLC entropy for the H.264 intra path.

Round 1 kept CAVLC on the host, which meant pulling the full quantized
level tensors (~8 MB/frame of int32) across the host<->device link every
frame — the entire 1 s/frame p50 (VERDICT weak #1).  This module moves the
whole entropy stage onto the TPU:

1. Every 4x4 residual block (27 per MB: luma DC, 16 luma AC, 2 chroma DC,
   8 chroma AC) is CAVLC-coded into a fixed layout of 34 ``(value, length)``
   codeword *slots* (length 0 = slot unused).  The per-block sequential
   pieces of ITU-T H.264 §9.2 — trailing-one detection, the adaptive
   ``suffixLength`` level loop, and the ``zerosLeft`` run_before loop — are
   fixed 16/15-step ``lax.scan``s whose state is vectorized over *all*
   blocks of the frame at once (~220k lanes at 1080p: ideal VPU shape).
   Nonzero coefficients are compacted into reverse scan order by a dense
   cumsum-rank one-hot reduction (argsort and in-scan gathers measured
   ~10x slower than dense selects on TPU).
2. nC contexts (§9.2.1) are pure neighbor shifts over the per-block
   total_coeff grids — no sequencing at all, because the slice-per-MB-row
   structure (ops/h264_device.py) removes cross-row dependencies.
3. Bits are concatenated scatter-free, each row's RBSP from a word of its
   own, into one flat buffer with a small metadata header in front, so the
   host fetches metadata + bitstream in a single bucketed pull and only
   does emulation-prevention escaping + Annex-B NAL wrapping
   (:func:`pack_frame`, for I and P pictures alike).  On the TPU by
   :mod:`.cabac_pack`'s two kernels (merge stages in VMEM, rows to their
   word offsets by DMA; PR 31); everywhere else by the :mod:`.bitmerge`
   hierarchy (slots -> 256-bit block buffers -> 2048-bit MB buffers by
   dense mask reductions -> rows by a barrel-shift reduction tree) and an
   output-sized gather: the same bytes, and the tests' oracle.
4. Pathological content that overflows the static block/MB caps or the
   flat buffer sets a per-frame flag (the same flag in both forms) and the
   caller falls back to host entropy (never at sane qp; correctness is
   never silently lost).

The pure-Python reference (bitstream/cavlc.py, bitstream/h264_entropy.py)
defines the contract; tests enforce byte-identical output.

Replaces the entropy half of NVENC (reference Dockerfile:210 selects
``nvh264enc``; SURVEY.md §7 "hard part #1" is exactly this stage).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..bitstream import cavlc as ref
from . import bitmerge, cabac_pack

# ---------------------------------------------------------------------------
# Dense constant tables (padded to uniform shapes for device gathers)
# ---------------------------------------------------------------------------

_I32 = np.int32


def _build_ct_tables():
    """coeff_token as (5, 17, 4) length/bits arrays.

    Classes: 0..2 = VLC by nC range, 3 = nC>=8 six-bit FLC, 4 = chroma DC.
    """
    ln = np.zeros((5, 17, 4), _I32)
    bi = np.zeros((5, 17, 4), _I32)
    for cls in range(3):
        ln[cls] = np.asarray(ref._CT_LEN[cls], _I32).reshape(17, 4)
        bi[cls] = np.asarray(ref._CT_BITS[cls], _I32).reshape(17, 4)
    for tc in range(17):
        for t1 in range(min(tc, 3) + 1):
            l, b = ref._ct_flc(tc, t1)
            ln[3, tc, t1], bi[3, tc, t1] = l, b
    ln[4, :5] = np.asarray(ref._CT_LEN_CDC, _I32).reshape(5, 4)
    bi[4, :5] = np.asarray(ref._CT_BITS_CDC, _I32).reshape(5, 4)
    return ln, bi


def _build_tz_tables():
    """total_zeros: luma (16, 16) and chroma-DC (3, 4), [TotalCoeff-1][tz]."""
    ln = np.zeros((16, 16), _I32)
    bi = np.zeros((16, 16), _I32)
    for i, (lens, bits) in enumerate(zip(ref._TZ_LEN, ref._TZ_BITS)):
        ln[i, :len(lens)] = lens
        bi[i, :len(bits)] = bits
    lnc = np.zeros((3, 4), _I32)
    bic = np.zeros((3, 4), _I32)
    for i, (lens, bits) in enumerate(zip(ref._TZ_LEN_CDC, ref._TZ_BITS_CDC)):
        lnc[i, :len(lens)] = lens
        bic[i, :len(bits)] = bits
    return ln, bi, lnc, bic


def _build_rb_tables():
    """run_before: (7, 15) indexed [min(zerosLeft,7)-1][run]."""
    ln = np.zeros((7, 15), _I32)
    bi = np.zeros((7, 15), _I32)
    for i, (lens, bits) in enumerate(zip(ref._RB_LEN, ref._RB_BITS)):
        ln[i, :len(lens)] = lens
        bi[i, :len(bits)] = bits
    return ln, bi


_CT_LEN, _CT_BITS = _build_ct_tables()
_TZ_LEN, _TZ_BITS, _TZC_LEN, _TZC_BITS = _build_tz_tables()
_RB_LEN, _RB_BITS = _build_rb_tables()

# Packed (length << 16 | bits) variants: every VLC here has bits < 2^16
# and length <= 32, so one one-hot lookup recovers both — halving the
# dominant broadcast-compare cost of code_blocks (the 4K profile put the
# paired lookups at ~1/3 of the whole CAVLC slot stage).
def _pack_lb(len_tab, bits_tab):
    ln = np.asarray(len_tab, np.int64)
    bi = np.asarray(bits_tab, np.int64)
    assert (bi < (1 << 16)).all() and (ln <= 32).all()
    return ((ln << 16) | bi).astype(np.int32)


_CT_PACKED = _pack_lb(_CT_LEN, _CT_BITS)
_TZ_PACKED = _pack_lb(_TZ_LEN, _TZ_BITS)
_TZC_PACKED = _pack_lb(_TZC_LEN, _TZC_BITS)

# run_before packed table, shrunk to the 57 live entries: zerosLeft <= 6
# rows only reach run <= 6 (a zero-gap cannot exceed the zeros left), so
# rows 0..5 need 7 slots each and only the zl > 6 row needs all 15.
_RB_PACKED = np.zeros(57, np.int32)
for _row in range(6):
    for _run in range(7):
        _RB_PACKED[_row * 7 + _run] = int(
            _pack_lb(_RB_LEN[_row, _run], _RB_BITS[_row, _run]))
for _run in range(15):
    _RB_PACKED[42 + _run] = int(_pack_lb(_RB_LEN[6, _run],
                                         _RB_BITS[6, _run]))

# Exp-Golomb ue(v) as (value, length) for codeNum 0..63 — covers mb_type
# (<= 25) and coded_block_pattern codeNum (<= 47).
_UE_VAL = np.arange(1, 65, dtype=_I32)               # ue bit pattern = v+1
_UE_LEN = np.array([2 * int(v).bit_length() - 1 for v in _UE_VAL], _I32)

# bit_length table for the se(mb_qp_delta) slot (tune=hq): |delta| <= 51
# bounds the ue codeNum at 102, pattern codeNum+1 <= 103 < 256.
_SE_BITLEN = np.array([max(v, 1).bit_length() for v in range(256)], _I32)


def se_slots(v):
    """Vectorized signed Exp-Golomb: int32 array (|v| <= ~100) ->
    (value, length) slot arrays."""
    v = jnp.asarray(v, jnp.int32)
    code = jnp.where(v > 0, 2 * v - 1, -2 * v)       # ue codeNum
    pat = code + 1                                   # ue bit pattern
    n = jnp.asarray(_SE_BITLEN)[jnp.clip(pat, 0, 255)]
    return pat.astype(jnp.uint32), 2 * n - 1

# MB-syntax slot layout (stream order, spec 7.3.5):
#   [0]      mb_type
#   [1..16]  I_NxN per-block mode signaling (prev flag / 4-bit rem)
#   [17]     intra_chroma_pred_mode ue(0)
#   [18]     coded_block_pattern (I_NxN only; folded into mb_type for I16)
#   [19]     mb_qp_delta se(0) (absent for an I_NxN MB with cbp == 0)
MB_SYN_SLOTS = 20

# Number of (value, length) slots per coded block.
BLOCK_SLOTS = 1 + 1 + 16 + 1 + 15      # coeff_token, T1 signs, levels, tz, rb
MB_BLOCKS = 27                         # 1 lumaDC + 16 lumaAC + 2 cDC + 8 cAC

# Flat output layout: metadata words, then the compacted bitstream.
META_WORDS = 1024          # [0]=flags, [1]=total_words, [2:2+R]=row_bytes,
MAX_META_ROWS = 510        # [2+510:2+510+R]=row word offsets (8K = 270 rows ok)
FLAT_CAP_WORDS = 1 << 17   # 512 KiB bitstream cap (overflow flag if exceeded)


# ---------------------------------------------------------------------------
# Vectorized level VLC (§9.2.2.1) — single <=32-bit slot per level
# ---------------------------------------------------------------------------

def _level_vlc(code, sl):
    """(value, length) of one level codeword; vectorized.

    ``code`` is the levelCode (>=0), ``sl`` the current suffixLength.  All
    prefix-escape tiers up to level_prefix 17 are covered, bounding the
    codeword at 32 bits — sufficient for any level reachable from 8-bit
    residuals (|level| < 2^13; exercised by the qp=1 checkerboard test).
    """
    code = code.astype(jnp.int32)
    sl = sl.astype(jnp.int32)

    # sl == 0 tiers
    z_short_v = jnp.uint32(1)
    z_short_l = code + 1                                    # code < 14
    z_esc4_v = ((1 << 4) | (code - 14)).astype(jnp.uint32)  # 14 <= code < 30
    z_esc4_l = jnp.int32(19)                                # 15 + 4

    # sl > 0 regular tier
    prefix = code >> jnp.maximum(sl, 1)
    suffix_mask = (1 << jnp.maximum(sl, 1)) - 1
    r_v = ((1 << jnp.maximum(sl, 1)) | (code & suffix_mask)).astype(jnp.uint32)
    r_l = prefix + 1 + sl

    # common escape tiers; extra = 15 iff sl == 0
    extra = jnp.where(sl == 0, 15, 0)
    esc_base = (15 << sl) + extra
    e12_v = ((1 << 12) | (code - esc_base)).astype(jnp.uint32)  # prefix 15
    e12_l = jnp.int32(28)                                   # 16 + 12
    b16 = esc_base + (1 << 13) - 4096                       # prefix 16
    e13_v = ((1 << 13) | (code - b16)).astype(jnp.uint32)
    e13_l = jnp.int32(30)                                   # 17 + 13
    b17 = esc_base + (1 << 14) - 4096                       # prefix 17
    e14_v = ((1 << 14) | (code - b17)).astype(jnp.uint32)
    e14_l = jnp.int32(32)                                   # 18 + 14

    in_esc12 = code < esc_base + 4096
    in_esc13 = code < b16 + (1 << 13)
    esc_v = jnp.where(in_esc12, e12_v, jnp.where(in_esc13, e13_v, e14_v))
    esc_l = jnp.where(in_esc12, e12_l, jnp.where(in_esc13, e13_l, e14_l))

    v0 = jnp.where(code < 14, z_short_v,
                   jnp.where(code < 30, z_esc4_v, esc_v))
    l0 = jnp.where(code < 14, z_short_l,
                   jnp.where(code < 30, z_esc4_l, esc_l))
    vp = jnp.where(prefix < 15, r_v, esc_v)
    lp = jnp.where(prefix < 15, r_l, esc_l)

    value = jnp.where(sl == 0, v0, vp)
    length = jnp.where(sl == 0, l0, lp)
    return value.astype(jnp.uint32), length


# ---------------------------------------------------------------------------
# Block coder: levels -> 34 slots, vectorized over all blocks
# ---------------------------------------------------------------------------

def _onehot_lookup(table: np.ndarray, idx, active=None):
    """Small-table lookup as a dense one-hot select-reduce.

    A vectorized gather on TPU runs at ~130M elements/s (measured on v5e:
    1.7 ms per 220k-lane lookup — it was the single hottest op in this
    module's first profile, 15 of them inside the run_before scan).  A
    broadcast compare against the table index domain is pure VPU work that
    XLA fuses to ~nothing for tables this small (<= a few hundred entries).
    """
    flat = np.asarray(table).reshape(-1)
    n = flat.shape[0]
    ii = idx.astype(jnp.int32)[..., None]
    sel = ii == jnp.arange(n, dtype=jnp.int32)
    if active is not None:
        sel = sel & active[..., None]
    return jnp.where(sel, jnp.asarray(flat), 0).sum(axis=-1)


def code_blocks(levels, nc, is_cdc, max_coeff):
    """CAVLC-code N blocks at once.

    levels:    (N, 16) int32, scan order; entries >= ``max_coeff`` must be 0.
    nc:        (N,) int32 nC context (ignored where is_cdc).
    is_cdc:    (N,) bool — chroma-DC blocks (nC == -1 tables, maxNumCoeff 4).
    max_coeff: (N,) int32 in {4, 15, 16}.

    Returns (values, lengths): (N, 34) uint32 / int32 slot arrays.  The
    caller zeroes lengths of blocks that are not coded at all (cbp gating);
    a *coded* all-zero block correctly emits its 1-slot coeff_token here.
    """
    levels = levels.astype(jnp.int32)
    idx16 = jnp.arange(16, dtype=jnp.int32)

    mask = levels != 0
    csum = bitmerge.cumsum_mm(mask.astype(jnp.int32))
    total = csum[:, -1].astype(jnp.int32)                   # (N,)

    # Dense compaction into REVERSE scan order (highest frequency first):
    # nonzero i has rank csum[i]-1; its reverse index is total-1-rank.
    revj = jnp.where(mask, total[:, None] - csum, -1)       # (N, 16)
    onehot = revj[:, :, None] == idx16                      # (N, 16, 16)
    rev_vals = jnp.where(onehot, levels[:, :, None], 0).sum(axis=1)
    rev_pos = jnp.where(onehot, idx16[None, :, None], 0).sum(axis=1)
    # rev_vals[:, j] / rev_pos[:, j]: value/scan-pos of the j-th nonzero
    # counting back from the highest-frequency coefficient (j < total).

    # --- trailing ones (up to 3 final +-1s in scan order) ---
    v0, v1, v2 = rev_vals[:, 0], rev_vals[:, 1], rev_vals[:, 2]
    c0 = (total > 0) & (jnp.abs(v0) == 1)
    c1 = c0 & (total > 1) & (jnp.abs(v1) == 1)
    c2 = c1 & (total > 2) & (jnp.abs(v2) == 1)
    t1 = c0.astype(jnp.int32) + c1.astype(jnp.int32) + c2.astype(jnp.int32)

    # --- coeff_token ---
    cls = jnp.where(is_cdc, 4,
                    jnp.where(nc < 2, 0,
                              jnp.where(nc < 4, 1, jnp.where(nc < 8, 2, 3))))
    ct_idx = (cls * 17 + total) * 4 + t1
    ct_packed = _onehot_lookup(_CT_PACKED, ct_idx)
    ct_len = ct_packed >> 16
    ct_bits = (ct_packed & 0xFFFF).astype(jnp.uint32)

    # --- trailing-one signs, highest frequency first (one slot) ---
    s0 = (v0 < 0).astype(jnp.uint32)
    s1 = (v1 < 0).astype(jnp.uint32)
    s2 = (v2 < 0).astype(jnp.uint32)
    sign_val = jnp.where(t1 == 1, s0,
                         jnp.where(t1 == 2, (s0 << 1) | s1,
                                   (s0 << 2) | (s1 << 1) | s2)).astype(jnp.uint32)
    sign_val = jnp.where(t1 > 0, sign_val, 0)

    # --- remaining levels, highest frequency first (16-step scan) ---
    # The j-th emitted level is reverse-index (t1 + j); pre-shift the
    # reversed array by t1 (0..3) so the scan consumes plain xs slices.
    def shift_left(a, k):
        return jnp.pad(a[:, k:], ((0, 0), (0, k)))

    lv_in = rev_vals
    for k in (1, 2, 3):
        lv_in = jnp.where((t1 == k)[:, None], shift_left(rev_vals, k), lv_in)
    n_levels = total - t1
    sl_init = jnp.where((total > 10) & (t1 < 3), 1, 0).astype(jnp.int32)

    # Statically unrolled (16 fixed steps): as a ``lax.scan`` this loop
    # was the single hottest region of the 4K profile (~10 ms/frame of
    # the 46 ms step — per-iteration carry round trips through HBM);
    # unrolled, XLA fuses the 16 bodies into a handful of kernels.
    n = levels.shape[0]
    sl = sl_init
    first = jnp.ones((n,), bool)
    vals_steps, lens_steps = [], []
    for j in range(16):
        level = lv_in[:, j]
        active = j < n_levels
        code = jnp.where(level > 0, 2 * level - 2, -2 * level - 1)
        code = code - jnp.where(first & (t1 < 3), 2, 0)
        value, length = _level_vlc(code, sl)
        lens_steps.append(jnp.where(active, length, 0))
        vals_steps.append(jnp.where(active, value, 0))
        sl_new = jnp.maximum(sl, 1)
        sl_new = jnp.where(
            (jnp.abs(level) > (3 << jnp.maximum(sl_new - 1, 0)))
            & (sl_new < 6), sl_new + 1, sl_new)
        sl = jnp.where(active, sl_new, sl)
        first = first & ~active
    lv_vals = jnp.stack(vals_steps, axis=1)                 # (N, 16)
    lv_lens = jnp.stack(lens_steps, axis=1)

    # --- total_zeros ---
    tz = jnp.where(total > 0, rev_pos[:, 0] + 1 - total, 0)
    tzi = jnp.clip(total - 1, 0, 15)
    tzn_idx = tzi * 16 + jnp.clip(tz, 0, 15)
    tzc_idx = jnp.clip(tzi, 0, 2) * 4 + jnp.clip(tz, 0, 3)
    tz_packed = jnp.where(is_cdc,
                          _onehot_lookup(_TZC_PACKED, tzc_idx),
                          _onehot_lookup(_TZ_PACKED, tzn_idx))
    tz_len = tz_packed >> 16
    tz_bits = (tz_packed & 0xFFFF).astype(jnp.uint32)
    tz_emit = (total > 0) & (total < max_coeff)
    tz_len = jnp.where(tz_emit, tz_len, 0)
    tz_bits = jnp.where(tz_emit, tz_bits, 0)

    # --- run_before: NOT a loop, despite §9.2.3's sequential phrasing ---
    # run_before[k] is the zero-gap between consecutive nonzeros (a shifted
    # difference of scan positions) and zerosLeft[k] is tz minus the gaps
    # already emitted (an exclusive prefix sum) — both fully parallel.  The
    # first version of this module ran it as a 15-step lax.scan with two
    # per-step table gathers; the profiler put that scan at 36 ms of the
    # 67 ms 1080p frame (gathers, §_onehot_lookup).  This formulation is
    # byte-identical (the zerosLeft==0 early-out coincides with runs of 0:
    # once the zeros are spent, remaining gaps are empty) and costs ~nothing.
    rev_pos_next = shift_left(rev_pos, 1)
    k15 = jnp.arange(15, dtype=jnp.int32)
    run = jnp.clip(rev_pos[:, :15] - rev_pos_next[:, :15] - 1, 0, 14)
    zeros_left = tz[:, None] - bitmerge.cumsum_mm(run, inclusive=False)
    rb_active = (k15 <= (total - 2)[:, None]) & (zeros_left > 0)
    rb_row = jnp.clip(jnp.minimum(zeros_left, 7) - 1, 0, 6)
    # 57-entry packed domain: rows 0..5 hold run <= 6 (a gap can't
    # exceed the zeros left), the zl > 6 row holds run <= 14
    rb_idx = jnp.where(rb_row < 6,
                       rb_row * 7 + jnp.minimum(run, 6),
                       42 + run)
    rb_packed = _onehot_lookup(_RB_PACKED, rb_idx, active=rb_active)
    rb_lens = rb_packed >> 16
    rb_vals = (rb_packed & 0xFFFF).astype(jnp.uint32)

    values = jnp.concatenate([
        ct_bits[:, None], sign_val[:, None], lv_vals,
        tz_bits[:, None], rb_vals], axis=1)
    lengths = jnp.concatenate([
        ct_len[:, None], t1[:, None], lv_lens,
        tz_len[:, None], rb_lens], axis=1)
    return values.astype(jnp.uint32), lengths.astype(jnp.int32)


# ---------------------------------------------------------------------------
# nC context grids (§9.2.1), slice-per-row neighbor rules
# ---------------------------------------------------------------------------

def nc_grid(tc, left_from_prev_mb):
    """Vectorized nC for (R, C, B, B) per-block total_coeff grids.

    Mirrors bitstream/h264_entropy._nc_grid: the above-neighbor exists only
    within the MB (the MB above is in another slice); the left-neighbor
    crosses into the previous MB's rightmost block column.
    """
    na = jnp.zeros_like(tc)
    na_avail = jnp.zeros(tc.shape, bool)
    na = na.at[:, :, :, 1:].set(tc[:, :, :, :-1])
    na_avail = na_avail.at[:, :, :, 1:].set(True)
    na = na.at[:, 1:, :, 0].set(left_from_prev_mb[:, :-1])
    na_avail = na_avail.at[:, 1:, :, 0].set(True)
    nb = jnp.zeros_like(tc)
    nb_avail = jnp.zeros(tc.shape, bool)
    nb = nb.at[:, :, 1:, :].set(tc[:, :, :-1, :])
    nb_avail = nb_avail.at[:, :, 1:, :].set(True)
    both = na_avail & nb_avail
    return jnp.where(both, (na + nb + 1) >> 1,
                     jnp.where(na_avail, na,
                               jnp.where(nb_avail, nb, 0))).astype(jnp.int32)


# luma4x4BlkIdx -> (bx, by); must match ops.h264_device.LUMA_BLOCK_ORDER.
_BLK_X = np.array([0, 1, 0, 1, 2, 3, 2, 3, 0, 1, 0, 1, 2, 3, 2, 3], _I32)
_BLK_Y = np.array([0, 0, 1, 1, 0, 0, 1, 1, 2, 2, 3, 3, 2, 2, 3, 3], _I32)


def blocks_first(a):
    """(R, C, B, ...) per-macroblock blocks -> (B, R * C, ...): the slot
    builders' block-major order."""
    return jnp.moveaxis(a, 2, 0).reshape(
        (a.shape[2], a.shape[0] * a.shape[1]) + a.shape[3:])


def frame_block_slots(levels: dict, slice_qp: int = None):
    """Level tensors (ops/h264_device.encode_intra_frame) -> per-block slots.

    Handles mixed I_16x16 / I_NxN macroblocks (``mb_i4``): I_NxN luma
    blocks carry 16-coefficient levels (``luma_i4``) with per-8x8 cbp
    gating and no Hadamard DC block.  Returns (values, lengths, syn_vals,
    syn_lens, qp_sum): (27, R * C, 34) codeword slots, block-major as
    :func:`pack_frame` takes them, plus the (R, C, 20) MB-syntax slots (see
    MB_SYN_SLOTS layout); ``qp_eff`` is the (R, C) plane of each
    macroblock's EFFECTIVE qp (``aq.qp_chain``: what a decoder holds as
    QPY there, and what the loop filter's thresholds follow; tune=hq, None
    otherwise), whose sum the host normalizes the rate model with.
    ``slice_qp`` (a Python int or a traced scalar) anchors the mb_qp_delta
    chain and is required when ``levels`` carries a ``qp_map``.
    """
    luma_dc = levels["luma_dc"]        # (R, C, 16) zigzag
    luma_ac = levels["luma_ac"]        # (R, C, 16, 15) blkIdx-ordered
    cb_dc = levels["cb_dc"]            # (R, C, 4)
    cb_ac = levels["cb_ac"]            # (R, C, 4, 15)
    cr_dc = levels["cr_dc"]
    cr_ac = levels["cr_ac"]
    nr, nc_mb = luma_dc.shape[:2]
    mb_i4 = jnp.asarray(levels.get(
        "mb_i4", np.zeros((nr, nc_mb), bool)))
    i4_modes = jnp.asarray(levels.get(
        "i4_modes", np.full((nr, nc_mb, 16), 2, np.int32)))
    luma_i4 = jnp.asarray(levels.get(
        "luma_i4", np.zeros((nr, nc_mb, 16, 16), np.int32)))

    cbp_luma = jnp.any(luma_ac != 0, axis=(2, 3))           # (R, C) I16
    grp_any = jnp.any(luma_i4.reshape(nr, nc_mb, 4, 4, 16) != 0,
                      axis=(3, 4))                          # (R, C, 4)
    cbp_luma4 = (grp_any.astype(jnp.int32)
                 * (1 << jnp.arange(4))).sum(axis=2)        # (R, C) I_NxN
    chroma_ac_any = (jnp.any(cb_ac != 0, axis=(2, 3))
                     | jnp.any(cr_ac != 0, axis=(2, 3)))
    chroma_dc_any = jnp.any(cb_dc != 0, axis=2) | jnp.any(cr_dc != 0, axis=2)
    cbp_chroma = jnp.where(chroma_ac_any, 2,
                           jnp.where(chroma_dc_any, 1, 0))  # (R, C)

    # --- per-block luma levels, gates and total_coeff grids ---
    grp_bit16 = grp_any[:, :, jnp.asarray(np.arange(16) // 4)]  # (R,C,16)
    luma_gate = jnp.where(mb_i4[:, :, None], grp_bit16,
                          cbp_luma[:, :, None])             # (R, C, 16)

    def pad16(a):
        """(..., k) -> (..., 16) zero-padded levels array."""
        k = a.shape[-1]
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 16 - k)])

    luma_lv = jnp.where(mb_i4[:, :, None, None], luma_i4,
                        pad16(luma_ac))                     # (R, C, 16, 16)

    tc_luma_blk = jnp.count_nonzero(luma_lv, axis=3).astype(jnp.int32)
    tc_luma_blk = tc_luma_blk * luma_gate
    tc_luma = jnp.zeros((nr, nc_mb, 4, 4), jnp.int32)
    tc_luma = tc_luma.at[:, :, jnp.asarray(_BLK_Y), jnp.asarray(_BLK_X)].set(
        tc_luma_blk)

    def chroma_tc(ac):
        t = jnp.count_nonzero(ac, axis=3).astype(jnp.int32)
        t = t * (cbp_chroma == 2)[:, :, None]
        return t.reshape(nr, nc_mb, 2, 2)

    tc_cb = chroma_tc(cb_ac)
    tc_cr = chroma_tc(cr_ac)

    ncl = nc_grid(tc_luma, tc_luma[:, :, :, 3])
    nccb = nc_grid(tc_cb, tc_cb[:, :, :, 1])
    nccr = nc_grid(tc_cr, tc_cr[:, :, :, 1])
    nc_dc = ncl[:, :, 0, 0]

    nmb = nr * nc_mb

    # The blocks are numbered BLOCK-major, index = block * macroblocks +
    # macroblock: code_blocks' (N, 34) results are then, column by column,
    # (27, R * C) planes with the long macroblock axis on the chip's lanes,
    # and slot b * 34 + k of every macroblock is one lane-dense row of what
    # the pack kernels read (:func:`pack_frame`).  Macroblock-major, the 27
    # had to leave the lanes through copies padded to 128 (PR 45).
    blk_levels = jnp.concatenate([
        pad16(luma_dc).reshape(1, nmb, 16),                 # lumaDC (I16)
        blocks_first(luma_lv),                              # 16 luma blocks
        pad16(cb_dc).reshape(1, nmb, 16),                   # cbDC
        pad16(cr_dc).reshape(1, nmb, 16),                   # crDC
        blocks_first(pad16(cb_ac)),                         # 4 cbAC
        blocks_first(pad16(cr_ac)),                         # 4 crAC
    ], axis=0)                                              # (27, R*C, 16)

    nc_luma_blk = ncl[:, :, jnp.asarray(_BLK_Y), jnp.asarray(_BLK_X)]
    nc_c = lambda g: blocks_first(g.reshape(nr, nc_mb, 4))
    blk_nc = jnp.concatenate([
        nc_dc.reshape(1, nmb), blocks_first(nc_luma_blk),
        jnp.zeros((2, nmb), jnp.int32),                     # chroma DC: nC=-1
        nc_c(nccb), nc_c(nccr)], axis=0)                    # (27, R*C)

    is_cdc = np.zeros(MB_BLOCKS, bool)
    is_cdc[17] = is_cdc[18] = True
    max_coeff = np.full(MB_BLOCKS, 15, _I32)
    max_coeff[0] = 16
    max_coeff[17:19] = 4
    luma_blk = np.zeros(MB_BLOCKS, bool)
    luma_blk[1:17] = True
    max_coeff = jnp.where(
        mb_i4.astype(bool).reshape(1, nmb) & luma_blk[:, None],
        16, max_coeff[:, None])

    values, lengths = code_blocks(
        blk_levels.reshape(MB_BLOCKS * nmb, 16),
        blk_nc.reshape(-1),
        jnp.asarray(np.repeat(is_cdc, nmb)),
        max_coeff.reshape(-1))
    values = values.reshape(MB_BLOCKS, nmb, BLOCK_SLOTS)
    lengths = lengths.reshape(MB_BLOCKS, nmb, BLOCK_SLOTS)

    # --- cbp gating: un-coded blocks emit nothing at all ---
    gate = jnp.concatenate([
        (~mb_i4).astype(bool).reshape(1, nmb),              # no DC for I_NxN
        blocks_first(luma_gate),
        jnp.stack([cbp_chroma > 0] * 2
                  + [cbp_chroma == 2] * 8).reshape(10, nmb)], axis=0)
    lengths = lengths * gate[:, :, None]

    # tune=hq: per-MB mb_qp_delta chained from the slice qp per row
    # (ops/aq.qp_chain).  The syntax exists for every I16 MB and for
    # I_NxN with cbp != 0 — exactly the MBs that dequantize anything.
    qp_se = None
    qp_eff = None
    if "qp_map" in levels:
        from . import aq
        with jax.named_scope("dngd.aq"):
            cbp_any = jnp.where(mb_i4, cbp_luma4 > 0, cbp_luma) \
                | (cbp_chroma > 0)
            codes = ~mb_i4 | cbp_any
            qp_eff, delta = aq.qp_chain(levels["qp_map"], codes, slice_qp)
        sv, sl = se_slots(delta)
        qp_se = (sv, jnp.where(codes, sl, 0))

    syn_vals, syn_lens = intra_mb_syntax_slots(
        levels["pred_mode"], mb_i4, i4_modes, cbp_luma, cbp_luma4,
        cbp_chroma, qp_se=qp_se)
    return values, lengths, syn_vals, syn_lens, qp_eff


def intra_mb_syntax_slots(pred_mode, mb_i4, i4_modes, cbp_luma, cbp_luma4,
                          cbp_chroma, qp_se=None):
    """Vectorized per-MB syntax slots (MB_SYN_SLOTS layout, spec 7.3.5).

    Mirrors bitstream/h264_entropy.encode_intra_picture's MB header
    emission, including the 8.3.1.1 min(A, B) Intra4x4PredMode predictor
    under slice-per-row neighbor rules.  ``qp_se`` (tune=hq): per-MB
    (value, length) override for the mb_qp_delta slot — lengths already
    gated to the MBs whose syntax carries it."""
    from ..bitstream.h264_entropy import _CBP_INTRA_TO_CODENUM

    nr, nc_mb = cbp_luma.shape
    mb_i4 = mb_i4.astype(bool)

    # raster-layout mode grid, 2 (DC) for non-I4 MBs
    modes_r = jnp.full((nr, nc_mb, 4, 4), 2, jnp.int32)
    modes_r = modes_r.at[:, :, jnp.asarray(_BLK_Y), jnp.asarray(_BLK_X)].set(
        jnp.where(mb_i4[:, :, None], i4_modes, 2))
    mode_a = jnp.full((nr, nc_mb, 4, 4), 2, jnp.int32)
    a_avail = jnp.zeros((nr, nc_mb, 4, 4), bool)
    mode_a = mode_a.at[:, :, :, 1:].set(modes_r[:, :, :, :-1])
    a_avail = a_avail.at[:, :, :, 1:].set(True)
    mode_a = mode_a.at[:, 1:, :, 0].set(modes_r[:, :-1, :, 3])
    a_avail = a_avail.at[:, 1:, :, 0].set(True)
    mode_b = jnp.full((nr, nc_mb, 4, 4), 2, jnp.int32)
    b_avail = jnp.zeros((nr, nc_mb, 4, 4), bool)
    mode_b = mode_b.at[:, :, 1:, :].set(modes_r[:, :, :-1, :])
    b_avail = b_avail.at[:, :, 1:, :].set(True)
    pred_i4 = jnp.where(a_avail & b_avail,
                        jnp.minimum(mode_a, mode_b), 2)     # (R, C, 4, 4)
    pred_blk = pred_i4[:, :, jnp.asarray(_BLK_Y), jnp.asarray(_BLK_X)]

    flag = i4_modes == pred_blk                             # (R, C, 16)
    rem = i4_modes - (i4_modes > pred_blk)
    mode_vals = jnp.where(flag, 1, rem).astype(jnp.uint32)
    mode_lens = jnp.where(mb_i4[:, :, None],
                          jnp.where(flag, 1, 4), 0)

    cl = cbp_luma.astype(jnp.int32)
    cc = cbp_chroma
    mbt16 = 1 + pred_mode + 4 * cc + 12 * cl                # codeNum, I16
    mbt_val = jnp.where(mb_i4, 1,
                        _onehot_lookup(_UE_VAL, mbt16)).astype(jnp.uint32)
    mbt_len = jnp.where(mb_i4, 1, _onehot_lookup(_UE_LEN, mbt16))

    cbp = cbp_luma4 + 16 * cc
    cbp_cn = _onehot_lookup(_CBP_INTRA_TO_CODENUM, cbp)
    cbp_val = _onehot_lookup(_UE_VAL, cbp_cn).astype(jnp.uint32)
    cbp_len = jnp.where(mb_i4, _onehot_lookup(_UE_LEN, cbp_cn), 0)

    chroma_val = jnp.ones((nr, nc_mb), jnp.uint32)          # ue(0)
    chroma_len = jnp.ones((nr, nc_mb), jnp.int32)
    if qp_se is None:
        qp_val = jnp.ones((nr, nc_mb), jnp.uint32)          # se(0)
        qp_len = jnp.where(mb_i4 & (cbp == 0), 0, 1)
    else:
        qp_val, qp_len = qp_se                              # tune=hq chain

    syn_vals = jnp.concatenate([
        mbt_val[:, :, None], mode_vals,
        chroma_val[:, :, None], cbp_val[:, :, None], qp_val[:, :, None]],
        axis=2)                                             # (R, C, 20)
    syn_lens = jnp.concatenate([
        mbt_len[:, :, None], mode_lens,
        chroma_len[:, :, None], cbp_len[:, :, None], qp_len[:, :, None]],
        axis=2)
    return syn_vals, syn_lens.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Hierarchical packing: slots -> blocks -> MBs -> row RBSPs -> flat buffer
# ---------------------------------------------------------------------------

HDR_SLOTS = 3          # slice header bits, pre-encoded on host (<= 96 bits)

# Metadata word carrying the frame's summed per-MB effective qp
# (tune=hq; 0 = uniform slice qp).  Rows claim [2, 2+MAX_META_ROWS) and
# [2+MAX_META_ROWS, 2+2*MAX_META_ROWS); this sits just past them.
META_QP_SUM_WORD = 2 + 2 * MAX_META_ROWS          # = 1022 < META_WORDS
# ... and the last word the P frame's macroblocks coded I_16x16 (tune=hq
# with the intra escape; 0 elsewhere): dngd_encoder_p_intra_mbs_total
META_P_INTRA_WORD = META_QP_SUM_WORD + 1          # = 1023


def pack_frame(values, lengths, syn_vals, syn_lens, hdr_vals, hdr_lens,
               trail_vals=None, trail_lens=None, qp_sum=None,
               p_intra_mbs=None):
    """Scatter-free packing of a picture's CAVLC slots into row RBSPs, for
    I and P pictures alike.

    values/lengths (B, R * C, 34): the blocks' slots in the builders'
    block-major order; syn_* (R, C, S): the macroblock layer's; hdr_*
    (R, HDR_SLOTS): each row's slice header;
    trail_* (R,): a P row's trailing skip run (length 0 where the row ends
    on a coded macroblock; None for an I picture).  Returns (flat,
    overflow) where ``flat`` is a (META_WORDS*4 + FLAT_CAP_WORDS*4,) uint8
    buffer: metadata words (flags, total words, per-row byte counts and
    word offsets) followed by the rows' RBSPs, each row starting at a
    4-byte-aligned offset.  ``qp_sum`` (tune=hq) rides in
    META_QP_SUM_WORD so the host's rate controller can normalize by the
    mean coded qp without an extra device pull; ``p_intra_mbs`` (a P
    frame's I_16x16 macroblocks) rides in META_P_INTRA_WORD the same way.

    The rows are merged by ``ops/cabac_pack``'s two kernels on the TPU and
    by the :mod:`.bitmerge` hierarchy everywhere else; the buffer is the
    same, byte for byte, and so is the overflow flag.
    """
    nr = syn_vals.shape[0]
    assert nr <= MAX_META_ROWS, "metadata header row capacity exceeded"
    if trail_lens is None:
        trail_vals = jnp.zeros(nr, jnp.uint32)
        trail_lens = jnp.zeros(nr, jnp.int32)
    rows = (_pack_rows_kernels if jax.default_backend() == "tpu"
            else _pack_rows_bitmerge)
    overflow, row_bits, flat_words = rows(
        values, lengths, syn_vals, syn_lens, hdr_vals, hdr_lens,
        trail_vals, trail_lens)

    row_bytes = row_bits // 8                               # byte-aligned
    row_words = (row_bytes + 3) // 4
    word_cum = jnp.cumsum(row_words)
    word_off = word_cum - row_words
    total_words = word_cum[-1]

    meta = jnp.zeros(META_WORDS, jnp.uint32)
    meta = meta.at[0].set(overflow.astype(jnp.uint32))
    meta = meta.at[1].set(total_words.astype(jnp.uint32))
    meta = meta.at[2:2 + nr].set(row_bytes.astype(jnp.uint32))
    meta = meta.at[2 + MAX_META_ROWS:2 + MAX_META_ROWS + nr].set(
        word_off.astype(jnp.uint32))
    if qp_sum is not None:
        meta = meta.at[META_QP_SUM_WORD].set(qp_sum.astype(jnp.uint32))
    if p_intra_mbs is not None:
        meta = meta.at[META_P_INTRA_WORD].set(
            p_intra_mbs.astype(jnp.uint32))

    with jax.named_scope("flat_bytes"):
        allw = jnp.concatenate([meta, flat_words])
        flat = jnp.stack([(allw >> 24) & 0xFF, (allw >> 16) & 0xFF,
                          (allw >> 8) & 0xFF, allw & 0xFF],
                         axis=-1).reshape(-1).astype(jnp.uint8)
    return flat, overflow


def _rbsp_stop(body_bits):
    """Length of a row's rbsp trailing bits: the stop bit and the zeros to
    the next byte, for a slice of ``body_bits`` bits."""
    return 1 + (8 - ((body_bits + 1) % 8)) % 8


def _pack_rows_bitmerge(values, lengths, syn_vals, syn_lens, hdr_vals,
                        hdr_lens, trail_vals, trail_lens):
    """The rows through the bitmerge hierarchy and an output-sized gather:
    (overflow, per-row bit counts, FLAT_CAP_WORDS words).  The packer
    wherever there is no TPU, and the kernels' oracle."""
    nr, nc_mb = syn_vals.shape[:2]
    # the hierarchy merges a macroblock's blocks: its (R, C, B, 34) view
    # of the block-major slots, a transpose no chip ever runs
    values, lengths = (
        jnp.moveaxis(a.reshape(-1, nr, nc_mb, BLOCK_SLOTS), 0, 2)
        for a in (values, lengths))

    with jax.named_scope("bitmerge_blocks"):
        # L1: each block's 34 slots -> 8-word buffer.
        blk_words, blk_bits, blk_ovf = bitmerge.slots_to_words(
            values, lengths, bitmerge.BLOCK_WORDS)          # (R,C,B,8)
        # MB syntax piece (an I macroblock's 20 slots are <= ~80 bits, a
        # P macroblock's skip_run..qp_delta <= ~40) -> 8-word buffer.
        syn_words, syn_bits, syn_ovf = bitmerge.slots_to_words(
            syn_vals, syn_lens, bitmerge.BLOCK_WORDS)       # (R,C,8)

    with jax.named_scope("bitmerge_mbs"):
        # L2: 1 + B pieces -> 64-word MB buffer.
        pieces = jnp.concatenate([syn_words[:, :, None, :], blk_words],
                                 axis=2)
        piece_bits = jnp.concatenate([syn_bits[:, :, None], blk_bits],
                                     axis=2)
        mb_words, mb_bits, mb_ovf = bitmerge.merge_pieces_dense(
            pieces, piece_bits, bitmerge.MB_WORDS)          # (R, C, 64)

    with jax.named_scope("bitmerge_rows"):
        # L3: header + C MBs + trailing run + rbsp trailing (+ padding to
        # a power of two) -> row RBSP.
        hdr_words4, hdr_bits, _ = bitmerge.slots_to_words(
            hdr_vals, hdr_lens, 4)                          # (R, 4)
        hdr_words = jnp.pad(hdr_words4,
                            ((0, 0), (0, bitmerge.MB_WORDS - 4)))

        # trailing skip run piece (<= 23 bits); the shift is guarded
        # because a zero-length piece would shift by 32 (undefined across
        # backends).
        trailrun_words = jnp.zeros((nr, bitmerge.MB_WORDS), jnp.uint32)
        trailrun_words = trailrun_words.at[:, 0].set(jnp.where(
            trail_lens > 0,
            trail_vals.astype(jnp.uint32)
            << (32 - jnp.maximum(trail_lens, 1)).astype(jnp.uint32),
            jnp.uint32(0)))

        # rbsp trailing: stop bit '1' + pad zeros; MSB-aligned that is
        # always 0x80000000 in word 0, only the *length* varies.
        stop_words = jnp.zeros((nr, bitmerge.MB_WORDS), jnp.uint32)
        stop_words = stop_words.at[:, 0].set(jnp.uint32(1) << 31)
        stop_bits = _rbsp_stop(hdr_bits + mb_bits.sum(axis=1) + trail_lens)

        n_pieces = 1 + nc_mb + 2                       # hdr, MBs, run, rbsp
        p2 = 1 << int(np.ceil(np.log2(n_pieces)))
        row_pieces = jnp.concatenate([
            hdr_words[:, None, :], mb_words,
            trailrun_words[:, None, :], stop_words[:, None, :],
            jnp.zeros((nr, p2 - n_pieces, bitmerge.MB_WORDS), jnp.uint32)],
            axis=1)
        row_bits_in = jnp.concatenate([
            hdr_bits[:, None], mb_bits, trail_lens[:, None],
            stop_bits[:, None], jnp.zeros((nr, p2 - n_pieces), jnp.int32)],
            axis=1)
        row_words_buf, row_bits = bitmerge.merge_pieces_tree(
            row_pieces, row_bits_in)                        # (R, p2*64)

    with jax.named_scope("bitmerge_gather"):
        row_words = (row_bits // 8 + 3) // 4
        word_cum = jnp.cumsum(row_words)                    # inclusive
        word_off = word_cum - row_words
        total_words = word_cum[-1]

        # Output-sized gather compaction: flat word j belongs to row
        # r(j) = #\{rows whose span ends at or before j\}.
        j = jnp.arange(FLAT_CAP_WORDS, dtype=jnp.int32)
        r = (j[:, None] >= word_cum[None, :]).sum(axis=1)
        rc = jnp.clip(r, 0, nr - 1)
        src = rc * row_words_buf.shape[1] + (j - word_off[rc])
        src = jnp.clip(src, 0, nr * row_words_buf.shape[1] - 1)
        flat_words = jnp.where(j < total_words,
                               row_words_buf.reshape(-1)[src], 0)

    overflow = (jnp.any(blk_ovf) | jnp.any(syn_ovf) | jnp.any(mb_ovf)
                | (total_words > FLAT_CAP_WORDS))
    return overflow, row_bits, flat_words


def _pack_rows_kernels(values, lengths, syn_vals, syn_lens, hdr_vals,
                       hdr_lens, trail_vals, trail_lens):
    """The same rows through ``cabac_pack.pack_rows_slot_major`` (the TPU's
    form): a macroblock is ONE run of slots, its syntax and then its
    blocks, the row's slice header in front of its first macroblock's and
    the trailing run and the rbsp trailing bits behind its last one's; the
    caps are the hierarchy's, tested on sums of lengths.  Value and length
    become kernel A's slot words where they lie, and the run is put together
    along the SLOT axis with the macroblocks on the lanes: slot b * 34 + k
    of every macroblock is a row of the builder's (B, R * C, 34) as it
    stands, so what kernel A reads is a permutation of major axes of what
    ``code_blocks`` made."""
    nr, nc_mb = syn_vals.shape[:2]
    nmb = nr * nc_mb
    lengths = lengths.astype(jnp.int32)
    syn_lens = syn_lens.astype(jnp.int32)
    hdr_lens = hdr_lens.astype(jnp.int32)
    blk_bits = lengths.sum(-1)                              # (B, R*C)
    syn_bits = syn_lens.sum(-1)
    mb_bits = syn_bits + blk_bits.sum(0).reshape(nr, nc_mb)
    caps_ovf = ((blk_bits > bitmerge.BLOCK_CAP_BITS).any(0).reshape(nr, nc_mb)
                | (syn_bits > bitmerge.BLOCK_CAP_BITS)
                | (mb_bits > bitmerge.MB_CAP_BITS))          # (R, C)

    # a header slot is 32 bits of the stream, a slot of the kernels holds
    # a value of 26: two halves
    hv = hdr_vals.astype(jnp.uint32)
    row_head_v = jnp.stack([hv >> 16, hv & 0xFFFF], -1).reshape(nr, -1)
    row_head_l = jnp.stack([jnp.maximum(hdr_lens - 16, 0),
                            jnp.minimum(hdr_lens, 16)], -1).reshape(nr, -1)
    stop_bits = _rbsp_stop(hdr_lens.sum(-1) + mb_bits.sum(-1) + trail_lens)
    row_tail_v = jnp.stack([trail_vals.astype(jnp.uint32),
                            jnp.uint32(1) << (stop_bits - 1)], -1)
    row_tail_l = jnp.stack([trail_lens, stop_bits], -1)

    col = jnp.arange(nc_mb)[None, None, :]

    def at_column(where, row_slots):
        """(R, K) slots of a row -> (K, R * C), in that column alone (a
        slot of length 0 elsewhere: the word 0)."""
        return jnp.where(col == where, row_slots.T[:, :, None],
                         0).reshape(-1, nmb)

    slots = jnp.concatenate([
        at_column(0, cabac_pack.slot_words(row_head_v, row_head_l)),
        jnp.moveaxis(cabac_pack.slot_words(syn_vals, syn_lens),
                     2, 0).reshape(-1, nmb),
        jnp.moveaxis(cabac_pack.slot_words(values, lengths),
                     2, 1).reshape(-1, nmb),
        at_column(nc_mb - 1, cabac_pack.slot_words(row_tail_v, row_tail_l)),
    ], axis=0)
    # a column's words: the macroblock's, a word a header slot, two behind
    col_cap = bitmerge.MB_WORDS + hdr_vals.shape[-1] + 2
    return cabac_pack.pack_rows_slot_major(slots, caps_ovf, col_cap,
                                           FLAT_CAP_WORDS)


# ---------------------------------------------------------------------------
# Fused frame encoder: RGB -> compacted CAVLC RBSP rows, one jit
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("pad_h", "pad_w", "qp", "with_recon",
                                    "i16_modes", "tune"))
def encode_intra_cavlc_frame(rgb, hdr_vals, hdr_lens, pad_h: int, pad_w: int,
                             qp: int, with_recon: bool = False,
                             i16_modes: str = "auto", tune: str = "off",
                             next_y=None):
    """Full device stage: RGB frame -> flat metadata+bitstream buffer.

    The host's only per-frame pull is a bucketed prefix of ``flat``.
    """
    from . import h264_device

    levels = h264_device.encode_intra_frame.__wrapped__(
        rgb, pad_h, pad_w, qp, i16_modes, tune, next_y)
    return _finish_cavlc(levels, hdr_vals, hdr_lens, with_recon, qp)


@functools.partial(jax.jit,
                   static_argnames=("qp", "with_recon", "i16_modes",
                                    "tune", "with_qp_eff"))
def encode_intra_cavlc_frame_yuv(y, cb, cr, hdr_vals, hdr_lens, qp: int,
                                 with_recon: bool = False,
                                 i16_modes: str = "auto",
                                 tune: str = "off", next_y=None,
                                 with_qp_eff: bool = False):
    """Device stage from pre-converted YUV 4:2:0 planes (host cv2 color
    conversion halves the host->device bytes; see
    h264_device.encode_intra_frame_yuv).  ``with_qp_eff`` (tune=hq with
    the loop filter on): the recon tuple ends with the (R, C) plane of
    effective qps the filter's thresholds follow."""
    from . import h264_device

    levels = h264_device.encode_intra_frame_yuv.__wrapped__(
        y, cb, cr, qp, i16_modes, tune, next_y)
    return _finish_cavlc(levels, hdr_vals, hdr_lens, with_recon, qp,
                         with_qp_eff)


#: The same stage with ``qp`` TRACED (tune "off" and "hq"): one compiled
#: program serves every qp the rate ladder can ask for.  With qp static a
#: 1080p program costs about a minute of host compile for the TPU and the
#: served CBR ladder has 15 qps — tens of minutes cold, and the compiler
#: of the installed libtpu does not survive several of those compiles
#: side by side (PERF.md Findings, PR 22).  Byte-identical to the
#: static-qp program at every qp (tests/test_h264_inter.py).
encode_intra_cavlc_frame_yuv_dynqp = jax.jit(
    encode_intra_cavlc_frame_yuv.__wrapped__,
    static_argnames=("with_recon", "i16_modes", "tune", "with_qp_eff"))


def _finish_cavlc(levels, hdr_vals, hdr_lens, with_recon: bool,
                  slice_qp: int = None, with_qp_eff: bool = False):
    recon = (levels["recon_y"], levels["recon_cb"], levels["recon_cr"])
    with jax.named_scope("dngd.slots"):
        values, lengths, syn_vals, syn_lens, qp_eff = frame_block_slots(
            levels, slice_qp)
        qp_sum = (None if qp_eff is None
                  else jnp.sum(qp_eff).astype(jnp.uint32))
    with jax.named_scope("dngd.pack"):
        flat, _ = pack_frame(values, lengths, syn_vals, syn_lens,
                             hdr_vals, hdr_lens, qp_sum=qp_sum)
    if with_recon:
        return flat, (recon + (qp_eff,) if with_qp_eff else recon)
    return flat


class FlatMeta:
    """Decoded metadata header of the flat buffer."""

    def __init__(self, meta_bytes: np.ndarray, nr: int):
        w = meta_bytes[:META_WORDS * 4].reshape(META_WORDS, 4).astype(np.uint32)
        words = (w[:, 0] << 24) | (w[:, 1] << 16) | (w[:, 2] << 8) | w[:, 3]
        self.overflow = bool(words[0])
        self.total_words = int(words[1])
        self.row_bytes = words[2:2 + nr].astype(np.int64)
        self.word_off = words[2 + MAX_META_ROWS:
                              2 + MAX_META_ROWS + nr].astype(np.int64)
        # tune=hq: summed per-MB effective qp (0 = uniform slice qp)
        self.qp_sum = int(words[META_QP_SUM_WORD])
        # ... and a P frame's macroblocks coded I_16x16 (0 elsewhere)
        self.p_intra_mbs = int(words[META_P_INTRA_WORD])


def slice_header_slots(nr: int, nc_mb: int, *, frame_num: int,
                       idr_pic_id: int = 0, qp_delta: int = 0,
                       slice_type: int = 7, idr: bool = True,
                       deblocking_idc: int = 1):
    """Pre-encode every row's slice header into HDR_SLOTS (value, length)
    pairs (host side; tiny).  Returns (R, 3) uint32 values / int32 lengths.
    ``slice_type``/``idr`` default to the IDR I-slice; pass (5, False) for
    the P path."""
    from ..bitstream import h264 as syn
    from ..bitstream.bitwriter import BitWriter

    vals = np.zeros((nr, HDR_SLOTS), np.uint32)
    lens = np.zeros((nr, HDR_SLOTS), np.int32)
    for r in range(nr):
        bw = BitWriter()
        syn.slice_header(bw, first_mb=r * nc_mb, slice_type=slice_type,
                         frame_num=frame_num, idr=idr,
                         idr_pic_id=idr_pic_id, qp_delta=qp_delta,
                         deblocking_idc=deblocking_idc)
        bits, nbits = bw.peek_bits()
        assert nbits <= 32 * HDR_SLOTS, "slice header exceeds slot budget"
        # split MSB-first into 32-bit chunks, right-aligned per slot
        rem = nbits
        for s in range(HDR_SLOTS):
            take = min(32, rem)
            if take <= 0:
                break
            shift = rem - take
            vals[r, s] = (bits >> shift) & ((1 << take) - 1)
            lens[r, s] = take
            rem -= take
    return vals, lens


def assemble_annexb(flat_host: np.ndarray, meta: FlatMeta,
                    *, headers: bytes = b"", nal_type: int = None,
                    ref_idc: int = 3) -> bytes:
    """Host side: the flat buffer's rows as Annex-B NALs behind ``headers``
    (IDR by default; (NAL_SLICE, 2) for P), each RBSP EPB-escaped: one
    native call over the pulled buffer (bitstream/h264.py
    ``annexb_rows``)."""
    from ..bitstream import h264 as syn

    return syn.annexb_rows(
        flat_host, META_WORDS * 4 + 4 * meta.word_off, meta.row_bytes,
        syn.NAL_IDR if nal_type is None else nal_type, ref_idc,
        prefix=headers)
