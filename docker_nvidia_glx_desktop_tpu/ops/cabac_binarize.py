"""Device-side CABAC binarization + context-index derivation.

Round 5 left the CABAC serving split as: device transform/quant +
``ops/level_pack`` compaction, host C doing EVERYTHING entropy — dense
level scan, binarization, ctxIdx derivation, arithmetic engine.  The
host stage measured 57-72 ms single-core at 1080p (BENCH_r05), which no
core count rescues to 60 fps without shrinking the per-row work.

This module moves binarization and ctxIdx computation onto the device:
a pure-JAX kernel walks the H.264 CABAC syntax (spec 9.3.2/9.3.3) for
every macroblock IN PARALLEL and emits a packed record stream — the
exact (bin, ctxIdx, bypass) sequence the arithmetic engine must
consume.  The slots are packed (``_pack_stream``) through the same
scatter-free bitmerge hierarchy level_pack uses wherever there is no TPU;
on the TPU that hierarchy's barrel-shifter stages were each a pass of the
worst-case-sized buffer through HBM (23 ms a 1080p frame whatever the
content), so there ``ops/cabac_pack.py`` packs the same slots into the same
buffer, word for word, with two Pallas kernels whose merge stages stay in
VMEM (``jax.default_backend()`` chooses, as ``ops/h264_deblock.py`` does
for the loop filter).  The host (native/cabac.cpp ``h264_cabac_engine_rows``) then runs
ONLY the arithmetic engine: read record, update range/low, emit bits.
No dense level tensors cross the link and the host never re-derives a
context.

Why this needs no sequential scans: under slice-per-MB-row every
context dependency is either *within* the MB (static block geometry) or
on the LEFT MB's *input data* (its levels/mv decide its cbf/cbp/skip/
mvd — never its coded output), so the whole derivation is shifts and
wheres over (R, C, ...) tensors.  Residual blocks are traced ONCE with
a leading block axis (16 luma / 8 chroma-AC blocks share one op set),
keeping the XLA graph small.

Record wire format (MSB-first bits inside each variable-length slot;
zero-length slots vanish — bitmerge drops them):

  DEC  ``0``   + ctx(9) + bin(1)             11 bits  one decision
  RUN  ``10``  + ctx(9) + cnt(4)             15 bits  cnt 1-bins on ctx
  BYP  ``110`` + cnt(4) + bits(cnt)        7+cnt bits bypass bins
  TRM  ``111`` + bin(1)                       4 bits  terminate

Transport layout (uint32 words; level_pack's shape with version 2 and
per-row BIT counts, so the engine knows exactly where a row's records
end — the zero-padded word tail must not read as a DEC record):

  [0] version (2)   [1] overflow flag   [2] total payload words
  [3] rows R        [4] slots per MB    [5..7] reserved
  [META_WORDS .. META_WORDS+R)   per-row payload BIT counts
  [META_WORDS+R ..)              row payloads, word-aligned

Overflow (a |level| beyond the suffix-slot budget, or a pathological
MB overrunning the static per-MB bit cap) sets the flag; the caller
falls back to the dense host coder for that frame — correctness never
depends on the fast path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import bitmerge, cabac_pack

__all__ = ["META_WORDS", "binarize_p", "binarize_intra", "split_rows",
           "header_words", "payload_words", "decode_records_py",
           "stitch_rows"]

META_WORDS = 8

# ctxBlockCat offsets (bitstream/cabac.py is the value source)
_CBF_OFF = {0: 0, 1: 4, 2: 8, 3: 12, 4: 16}
_SIG_OFF = {0: 0, 1: 15, 2: 29, 3: 44, 4: 47}
_ABS_OFF = {0: 0, 1: 10, 2: 20, 3: 30, 4: 39}

# luma4x4BlkIdx -> (bx, by) z-scan (bitstream/cabac._BLK_XY)
_BLK_XY = ((0, 0), (1, 0), (0, 1), (1, 1),
           (2, 0), (3, 0), (2, 1), (3, 1),
           (0, 2), (1, 2), (0, 3), (1, 3),
           (2, 2), (3, 2), (2, 3), (3, 3))

_U32 = jnp.uint32


def _u(x):
    return jnp.asarray(x).astype(_U32)


def _i(x):
    return jnp.asarray(x).astype(jnp.int32)


def _dec(ctx, b, pres=None):
    """DEC record: tag 0 + ctx(9) + bin(1)."""
    val = (_u(ctx) << 1) | _u(jnp.asarray(b).astype(bool))
    if pres is None:
        return val, jnp.broadcast_to(jnp.int32(11), val.shape)
    val, pres = jnp.broadcast_arrays(val, pres)
    return val, jnp.where(pres, 11, 0).astype(jnp.int32)


def _run(ctx, cnt, pres):
    """RUN record: tag 10 + ctx(9) + cnt(4): cnt decisions of bin=1."""
    val = (_u(2) << 13) | (_u(ctx) << 4) | _u(cnt)
    val, pres = jnp.broadcast_arrays(val, pres)
    return val, jnp.where(pres, 15, 0).astype(jnp.int32)


def _byp(bits, cnt, pres):
    """BYP record: tag 110 + cnt(4) + cnt literal bypass bins."""
    cnt = _u(cnt)
    val = (_u(6) << (4 + cnt)) | (cnt << cnt) | _u(bits)
    val, pres = jnp.broadcast_arrays(val, pres)
    return val, jnp.where(pres, 7 + _i(cnt), 0).astype(jnp.int32)


def _trm(b, pres=None):
    """TRM record: tag 111 + bin."""
    val = (_u(7) << 1) | _u(jnp.asarray(b).astype(bool))
    if pres is None:
        return val, jnp.broadcast_to(jnp.int32(4), val.shape)
    val, pres = jnp.broadcast_arrays(val, pres)
    return val, jnp.where(pres, 4, 0).astype(jnp.int32)


def _cat(a, b):
    """Concatenate two records into one slot (either may be absent)."""
    av, al = a
    bv, bl = b
    av, al, bv, bl = jnp.broadcast_arrays(av, al, bv, bl)
    val = (jnp.where(al > 0, av << bl.astype(_U32), 0)
           | jnp.where(bl > 0, bv, 0))
    return val.astype(_U32), (al + bl).astype(jnp.int32)


def _merge(a, b):
    """Merge two mutually-exclusive slot candidates (at most one has a
    nonzero length per MB) into one slot."""
    av, al = a
    bv, bl = b
    av, al, bv, bl = jnp.broadcast_arrays(av, al, bv, bl)
    return jnp.where(bl > 0, bv, av).astype(_U32), (al + bl)


class _Recs:
    """Slot accumulator: (R, C, k)-piece list concatenated at pack
    time, plus the STATIC per-MB maximum bit total (the L2 cap)."""

    def __init__(self, shape):
        self.shape = shape
        self.pieces = []
        self.max_bits = 0

    def add(self, rec, mx: int):
        v, ln = rec
        self.pieces.append(
            (jnp.broadcast_to(v, self.shape)[..., None].astype(_U32),
             jnp.broadcast_to(ln, self.shape)[..., None]
             .astype(jnp.int32)))
        self.max_bits += mx

    def add_batch(self, vals, lns, mx_total: int):
        """vals/lns (R, C, K): K pre-stacked slots in stream order."""
        self.pieces.append((vals.astype(_U32), lns.astype(jnp.int32)))
        self.max_bits += mx_total

    def stacked(self):
        return (jnp.concatenate([p[0] for p in self.pieces], axis=-1),
                jnp.concatenate([p[1] for p in self.pieces], axis=-1))


def _residual_slots(coeffs, cat: int, cbf_inc, emit):
    """Record slots for residual blocks (spec 9.3.3.1.3), traced once
    over arbitrary leading dims (batch the block axis!).

    coeffs (..., n) int32 zigzag; cbf_inc/emit (...,).  Returns
    (vals (..., S), lns (..., S), value_overflow (...,), max_bits) with
    S = 1 + (n-1) + 3n: cbf, sig+last pairs, then per-coefficient
    [first-prefix-bin][run+terminator][suffix+sign] in reverse scan
    order — exactly the engine's consumption order."""
    n = coeffs.shape[-1]
    nz = coeffs != 0
    cbf = nz.any(-1)
    idx = jnp.arange(n, dtype=jnp.int32)
    last_nz = jnp.max(jnp.where(nz, idx, -1), axis=-1)
    vals, lns = [], []
    maxb = 0

    def add(rec, mx):
        nonlocal maxb
        v, ln = rec
        vals.append(v)
        lns.append(ln)
        maxb += mx

    add(_dec(85 + _CBF_OFF[cat] + _i(cbf_inc), cbf, emit), 11)
    sig_base = 105 + _SIG_OFF[cat]
    last_base = 166 + _SIG_OFF[cat]
    for i in range(n - 1):
        inc = min(i, 2) if cat == 3 else i
        pres = emit & cbf & (i <= last_nz)
        d_sig = _dec(sig_base + inc, nz[..., i], pres)
        d_last = _dec(last_base + inc, last_nz == i, pres & nz[..., i])
        add(_cat(d_sig, d_last), 22)

    a = jnp.abs(coeffs)
    lvl = a - 1

    abs_base = 227 + _ABS_OFF[cat]
    capn = 3 if cat == 3 else 4
    prefix = jnp.minimum(lvl, 14)
    # UEG0 suffix (lvl >= 14) + sign, as bypass runs.  DC categories
    # (0, 3) carry the Hadamard-amplified magnitudes, so they get a
    # TWO-slot suffix budget (|level| <= 16398, past level_pack's own
    # +-16383 value cap); AC categories keep one slot (|level| <= 141 —
    # beyond it only at pathological qp, where the per-frame dense
    # fallback takes over).
    wide = cat in (0, 3)
    u_lim = 14 if wide else 6
    v = jnp.maximum(lvl - 14, 0)
    u = jnp.zeros_like(v)
    for k in range(1, u_lim + 2):
        u = u + (v + 1 >= (1 << k))
    u = jnp.minimum(u, u_lim)          # past-limit flags overflow below
    r = v - ((1 << u) - 1)
    sign = (coeffs < 0).astype(jnp.int32)
    suf = (((1 << u) - 1) << (u + 1)) | r
    has_suf = lvl >= 14
    bits = jnp.where(has_suf, (suf << 1) | sign, sign)
    cnt = jnp.where(has_suf, 2 * u + 2, 1)
    if wide:
        hi_len = jnp.minimum(cnt, 15)
        lo_len = cnt - hi_len
        hi_bits = bits >> lo_len
        lo_bits = bits & ((1 << lo_len) - 1)
    zero = jnp.zeros(coeffs.shape[:-1], bool)

    # Levels above 1 and equal to 1 at the scan positions behind j, as
    # running counts of this loop and NOT as a reversed ``jnp.cumsum``:
    # fused with what feeds and reads it, XLA:TPU counts that wrong (I16
    # AC blocks on the v5e: 4,875 words of a 1080p picture's stream, which
    # no decoder takes), and held apart by ``optimization_barrier``s it is
    # a ``reduce_window`` over a minor dimension of 16, 4.4 ms a frame
    # (PERF.md PR 28, PR 29).
    num_gt1 = num_eq1 = jnp.zeros(coeffs.shape[:-1], jnp.int32)
    for j in range(n - 1, -1, -1):            # reverse scan order
        nzj = emit & nz[..., j]
        c0 = abs_base + jnp.where(num_gt1 > 0, 0,
                                  jnp.minimum(4, 1 + num_eq1))
        cn = abs_base + 5 + jnp.minimum(capn, num_gt1)
        num_gt1 = num_gt1 + (a[..., j] > 1)
        num_eq1 = num_eq1 + (a[..., j] == 1)
        add(_dec(c0, lvl[..., j] >= 1, nzj), 11)
        run = _run(cn, jnp.clip(prefix[..., j] - 1, 1, 14),
                   nzj & (prefix[..., j] >= 2))
        term = _dec(cn, zero,
                    nzj & (prefix[..., j] >= 1) & (prefix[..., j] < 14))
        add(_cat(run, term), 26)
        if wide:
            add(_byp(hi_bits[..., j], hi_len[..., j], nzj), 22)
            add(_byp(lo_bits[..., j], jnp.maximum(lo_len[..., j], 1),
                     nzj & (lo_len[..., j] > 0)), 22)
        else:
            add(_byp(bits[..., j], cnt[..., j], nzj), 22)
    ovf = (emit[..., None] & nz
           & (jnp.maximum(lvl - 14, 0) + 1 > (1 << (u_lim + 1)) - 1)
           ).any(-1)
    return jnp.stack(vals, -1), jnp.stack(lns, -1), ovf, maxb


def _left(x):
    """Left-MB shift along the column axis (column 0 gets zeros)."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _chroma_cbp(cb_dc, cb_ac, cr_dc, cr_ac):
    c_dc = cb_dc.any(-1) | cr_dc.any(-1)
    c_ac = cb_ac.any((-2, -1)) | cr_ac.any((-2, -1))
    return jnp.where(c_ac, 2, jnp.where(c_dc, 1, 0))


def _raster_grid(blk16):
    """(R, C, 16) per-blkIdx values -> (R, C, 4, 4) raster [by][bx]."""
    nr, nc = blk16.shape[:2]
    g = jnp.zeros((nr, nc, 4, 4), blk16.dtype)
    for blk, (bx, by) in enumerate(_BLK_XY):
        g = g.at[..., by, bx].set(blk16[..., blk])
    return g


def _luma_cbf_inc(cbf_r, left_skip, col0, intra: bool):
    """ctxIdxInc of coded_block_flag for the 16 luma blocks, stacked
    (R, C, 16) in blkIdx order.  cbf_r (R, C, 4, 4) raster grid."""
    una = 1 if intra else 0
    left_c3 = [_left(cbf_r[..., by, 3].astype(jnp.int32))
               for by in range(4)]
    out = []
    for blk, (bx, by) in enumerate(_BLK_XY):
        if bx > 0:
            av = cbf_r[..., by, bx - 1].astype(jnp.int32)
        else:
            av = jnp.where(col0, una,
                           jnp.where(left_skip, 0, left_c3[by]))
        bv = (cbf_r[..., by - 1, bx].astype(jnp.int32) if by > 0
              else jnp.full_like(av, una))
        out.append(av + 2 * bv)
    return jnp.stack(out, -1)


def _chroma_slots(recs, cb_dc, cb_ac, cr_dc, cr_ac, cc, left_skip, col0,
                  emit_any, intra: bool):
    """Chroma DC (cat3) then AC (cat4) residual slots, coder order —
    both traced once over a stacked block axis."""
    una = 1 if intra else 0
    emit_dc = emit_any & (cc > 0)
    emit_ac = emit_any & (cc == 2)
    # DC: (R, C, 2, 4) -- cb then cr, matching _code_chroma order
    dc = jnp.stack([cb_dc, cr_dc], axis=2)
    dcnz = dc.any(-1).astype(jnp.int32)                  # (R, C, 2)
    a = jnp.where(col0[..., None], una,
                  jnp.where(left_skip[..., None], 0, _left(dcnz)))
    v, ln, ovf_dc, mx = _residual_slots(dc, 3, a + 2 * una,
                                        emit_dc[..., None])
    nr, nc = cc.shape
    recs.add_batch(v.reshape(nr, nc, -1), ln.reshape(nr, nc, -1),
                   2 * mx)
    # AC: (R, C, 8, 15) -- cb blocks 0..3 then cr blocks 0..3
    ac = jnp.concatenate([cb_ac, cr_ac], axis=2)
    acnz = ac.any(-1).astype(jnp.int32)                  # (R, C, 8)
    incs = []
    for p in range(2):
        for b in range(4):
            by, bx = divmod(b, 2)
            cur = acnz[..., p * 4:p * 4 + 4]
            if bx > 0:
                av = cur[..., by * 2]
            else:
                av = jnp.where(col0, una,
                               jnp.where(left_skip, 0,
                                         _left(cur[..., by * 2 + 1])))
            bv = cur[..., bx] if by > 0 else jnp.full_like(av, una)
            incs.append(av + 2 * bv)
    v, ln, ovf_ac, mx = _residual_slots(ac, 4, jnp.stack(incs, -1),
                                        emit_ac[..., None])
    recs.add_batch(v.reshape(nr, nc, -1), ln.reshape(nr, nc, -1),
                   8 * mx)
    return ovf_dc.any(-1) | ovf_ac.any(-1)


def _mvd_slots(recs, mvd_comp, s_left, base: int, pres):
    """mvd_l0 component: UEG3 uCoff=9 prefix (paired DECs) + suffix/
    sign bypass.  Returns the suffix-budget overflow mask."""
    inc = jnp.where(s_left < 3, 0, jnp.where(s_left <= 32, 1, 2))
    aa = jnp.abs(mvd_comp)
    prefix = jnp.minimum(aa, 9)
    ctxs = [base + inc, base + 3, base + 4, base + 5, base + 6]
    ds = []
    for k in range(9):
        pk = pres & ((k < prefix) | ((k == prefix) & (prefix < 9)))
        ds.append(_dec(ctxs[min(k, 4)], k < prefix, pk))
    for k in range(0, 8, 2):
        recs.add(_cat(ds[k], ds[k + 1]), 22)
    recs.add(ds[8], 11)
    v3 = jnp.maximum(aa - 9, 0)
    u3 = jnp.zeros_like(v3)
    for j in range(1, 7):
        u3 = u3 + (v3 >= 8 * ((1 << j) - 1))
    r3 = v3 - 8 * ((1 << u3) - 1)
    suf3 = (((1 << u3) - 1) << (u3 + 4)) | r3
    sign = (mvd_comp < 0).astype(jnp.int32)
    has_suf = aa >= 9
    bits = jnp.where(has_suf, (suf3 << 1) | sign, sign)
    cnt = jnp.where(has_suf, 2 * u3 + 5, 1)
    recs.add(_byp(bits, cnt, pres & (aa > 0)), 22)
    return pres & (2 * u3 + 5 > 15)


def _pack_stream(recs: _Recs, value_ovf):
    """Slot arrays -> bitmerge hierarchy -> version-2 transport buffer
    (per-row BIT counts in the meta table).  On the TPU the hierarchy is
    ``cabac_pack``'s two kernels; the buffer is the same, word for word."""
    vals, lns = recs.stacked()
    r, c, s = vals.shape
    s += (-s) % 8                       # slots merge eight to a piece
    p2 = 1 << int(np.ceil(np.log2(s // 8)))
    mb_cap = min(p2 * 8, -(-recs.max_bits // 32))
    c2 = 1 << int(np.ceil(np.log2(c)))
    if jax.default_backend() == "tpu":
        overflow, row_bits, payload = cabac_pack.pack_rows(
            vals, lns, value_ovf, mb_cap, r * c2 * mb_cap)
    else:
        overflow, row_bits, payload = _pack_rows_xla(
            vals, lns, value_ovf, mb_cap, p2, c2)
    row_words = ((row_bits + 31) >> 5).astype(jnp.int32)
    hdr = jnp.zeros(META_WORDS + r, jnp.uint32)
    hdr = (hdr.at[0].set(2)
           .at[1].set(overflow.astype(jnp.uint32))
           .at[2].set(row_words.sum().astype(jnp.uint32))
           .at[3].set(r).at[4].set(s)
           .at[META_WORDS:].set(row_bits.astype(jnp.uint32)))
    return jnp.concatenate([hdr, payload])


def _pack_rows_xla(vals, lns, value_ovf, mb_cap: int, p2: int, c2: int):
    """(overflow, per-row bits, payload) through the bitmerge hierarchy:
    the packer wherever there is no TPU, and the kernels' oracle."""
    r, c, s = vals.shape
    pad = (-s) % 8
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, pad)))
        lns = jnp.pad(lns, ((0, 0), (0, 0), (0, pad)))
        s += pad
    nb = s // 8
    w1, nb1, _ = bitmerge.slots_to_words(
        vals.reshape(r, c, nb, 8), lns.reshape(r, c, nb, 8), 8)
    w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, p2 - nb), (0, 0)))
    nb1 = jnp.pad(nb1, ((0, 0), (0, 0), (0, p2 - nb)))
    w2, mb_bits = bitmerge.merge_pieces_tree(w1, nb1)
    overflow = value_ovf.any() | (mb_bits > 32 * mb_cap).any()
    w2 = w2[..., :mb_cap]
    w2 = jnp.pad(w2, ((0, 0), (0, c2 - c), (0, 0)))
    mb_bits = jnp.pad(mb_bits, ((0, 0), (0, c2 - c)))
    w3, row_bits = bitmerge.merge_pieces_tree(w2, mb_bits)
    row_words = ((row_bits + 31) >> 5).astype(jnp.int32)
    row_cap = w3.shape[-1]
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(row_words)])[:r]
    payload = jnp.zeros(r * row_cap, jnp.uint32)

    def body(i, acc):
        return jax.lax.dynamic_update_slice(
            acc, jax.lax.dynamic_index_in_dim(w3, i, keepdims=False),
            (offs[i],))

    return overflow, row_bits, jax.lax.fori_loop(0, r, body, payload)


@jax.jit
@jax.named_scope("dngd.binarize")
def binarize_p(mv, luma, cb_dc, cb_ac, cr_dc, cr_ac):
    """Record stream for a P picture (P_L0_16x16 + P_Skip subset).

    Shapes as ops/h264_inter output (mv (R,C,2) quarter-pel (y, x),
    luma (R,C,16,16) zigzag, chroma DC/AC).  Returns the transport
    buffer the host engine replays row by row."""
    mv = _i(mv)
    luma = _i(luma)
    cb_dc, cb_ac = _i(cb_dc), _i(cb_ac)
    cr_dc, cr_ac = _i(cr_dc), _i(cr_ac)
    nr, nc = luma.shape[:2]
    recs = _Recs((nr, nc))
    col0 = jnp.broadcast_to(jnp.arange(nc) == 0, (nr, nc))

    lnz = luma.any(-1)                                 # (R, C, 16)
    grp = lnz.reshape(nr, nc, 4, 4).any(-1)            # (R, C, 4) 8x8
    cbp_luma = (grp * (1 << jnp.arange(4))).sum(-1)
    cc = _chroma_cbp(cb_dc, cb_ac, cr_dc, cr_ac)
    skip = (mv == 0).all(-1) & (cbp_luma == 0) & (cc == 0)
    left_skip = _left(skip)
    ns = ~skip

    mvp = _left(mv)                 # left MB's mv (a skip left's is 0)
    mvd = mv - mvp
    absmvd = jnp.abs(mvd)
    labs = _left(jnp.where(skip[..., None], 0, absmvd))

    # mb_skip_flag
    inc_skip = ((~col0) & (~left_skip)).astype(jnp.int32)
    recs.add(_dec(11 + inc_skip, skip), 11)
    # mb_type P_L0_16x16: "000" on ctx 14, 15, 16
    f = jnp.zeros((nr, nc), bool)
    recs.add(_cat(_dec(14, f, ns), _dec(15, f, ns)), 22)
    recs.add(_dec(16, f, ns), 11)
    # mvd_l0: comp 0 = x (mv[..., 1]), comp 1 = y (mv[..., 0])
    ovf = _mvd_slots(recs, mvd[..., 1], labs[..., 1], 40, ns)
    ovf |= _mvd_slots(recs, mvd[..., 0], labs[..., 0], 47, ns)
    # coded_block_pattern
    lcl = _left(jnp.where(skip, 0, cbp_luma))
    lcc = _left(jnp.where(skip, 0, cc))
    cbp_d = []
    for b in range(4):
        if b & 1:
            a_n = 1 - grp[..., b - 1].astype(jnp.int32)
        else:
            a_n = jnp.where(col0, 0, 1 - ((lcl >> (b + 1)) & 1))
        b_n = (1 - grp[..., b - 2].astype(jnp.int32)) if b & 2 \
            else jnp.zeros((nr, nc), jnp.int32)
        cbp_d.append(_dec(73 + a_n + 2 * b_n, grp[..., b], ns))
    recs.add(_cat(cbp_d[0], cbp_d[1]), 22)
    recs.add(_cat(cbp_d[2], cbp_d[3]), 22)
    d1 = _dec(77 + (lcc > 0).astype(jnp.int32), cc > 0, ns)
    d2 = _dec(81 + (lcc == 2).astype(jnp.int32), cc == 2,
              ns & (cc > 0))
    recs.add(_cat(d1, d2), 22)
    # mb_qp_delta (always 0; prev MB's delta is 0 too -> ctx 60)
    recs.add(_dec(60, f, ns & ((cbp_luma > 0) | (cc > 0))), 11)
    # luma residuals, all 16 blocks in one traced batch
    incs = _luma_cbf_inc(_raster_grid(lnz), left_skip, col0,
                         intra=False)
    emit16 = ns[..., None] & jnp.repeat(grp, 4, axis=-1)
    v, ln, ov, mx = _residual_slots(luma, 2, incs, emit16)
    recs.add_batch(v.reshape(nr, nc, -1), ln.reshape(nr, nc, -1),
                   16 * mx)
    ovf |= ov.any(-1)
    # chroma residuals
    ovf |= _chroma_slots(recs, cb_dc, cb_ac, cr_dc, cr_ac, cc,
                         left_skip, col0, ns, intra=False)
    # end_of_slice_flag
    recs.add(_trm(jnp.broadcast_to(jnp.arange(nc) == nc - 1,
                                   (nr, nc))), 4)
    return _pack_stream(recs, ovf)


@jax.jit
@jax.named_scope("dngd.binarize")
def binarize_intra(luma_dc, luma_ac, cb_dc, cb_ac, cr_dc, cr_ac,
                   pred_mode, mb_i4, i4_modes, luma_i4):
    """Record stream for an I picture (I_16x16 + I_NxN subset)."""
    luma_dc, luma_ac = _i(luma_dc), _i(luma_ac)
    cb_dc, cb_ac = _i(cb_dc), _i(cb_ac)
    cr_dc, cr_ac = _i(cr_dc), _i(cr_ac)
    pred_mode = _i(pred_mode)
    mb_i4 = jnp.asarray(mb_i4).astype(bool)
    i4_modes = _i(i4_modes)
    luma_i4 = _i(luma_i4)
    nr, nc = luma_dc.shape[:2]
    recs = _Recs((nr, nc))
    col0 = jnp.broadcast_to(jnp.arange(nc) == 0, (nr, nc))
    f = jnp.zeros((nr, nc), bool)
    left_skip = f                                  # no skip in I slices

    cl16 = luma_ac.any((-2, -1))                   # I16 AC coded flag
    i4nz = luma_i4.any(-1)                         # (R, C, 16)
    grp4 = i4nz.reshape(nr, nc, 4, 4).any(-1)      # (R, C, 4)
    cbp4 = (grp4 * (1 << jnp.arange(4))).sum(-1)
    cc = _chroma_cbp(cb_dc, cb_ac, cr_dc, cr_ac)
    i16 = ~mb_i4

    # mb_type prefix: ctx 3 + (left available && left is I_16x16)
    linc = ((~col0) & _left(i16)).astype(jnp.int32)
    recs.add(_dec(3 + linc, i16), 11)
    # I_16x16 suffix: not-PCM terminate + cbp/pred bins
    recs.add(_trm(f, i16), 4)
    recs.add(_cat(_dec(6, cl16, i16), _dec(7, cc > 0, i16)), 22)
    recs.add(_dec(8, cc == 2, i16 & (cc > 0)), 11)
    recs.add(_cat(_dec(9, (pred_mode >> 1) & 1, i16),
                  _dec(10, pred_mode & 1, i16)), 22)
    # I_NxN: prev_intra4x4_pred_mode + rem bins (8.3.1.1 predictors)
    modes_r = _raster_grid(jnp.where(mb_i4[..., None], i4_modes, 2))
    left_m3 = [_left(modes_r[..., by, 3]) for by in range(4)]
    for blk, (bx, by) in enumerate(_BLK_XY):
        if bx > 0:
            ma = modes_r[..., by, bx - 1]
            ava = jnp.ones((nr, nc), bool)
        else:
            ma = jnp.where(col0, 2, left_m3[by])
            ava = ~col0
        if by > 0:
            mb_, avb = modes_r[..., by - 1, bx], jnp.ones((nr, nc), bool)
        else:
            mb_, avb = jnp.full((nr, nc), 2), f
        pred = jnp.where(ava & avb, jnp.minimum(ma, mb_), 2)
        mode = i4_modes[..., blk]
        eq = mode == pred
        rem = jnp.where(mode > pred, mode - 1, mode)
        e4 = mb_i4
        recs.add(_cat(_dec(68, eq, e4), _dec(69, rem & 1, e4 & ~eq)),
                 22)
        recs.add(_cat(_dec(69, (rem >> 1) & 1, e4 & ~eq),
                      _dec(69, (rem >> 2) & 1, e4 & ~eq)), 22)
    # intra_chroma_pred_mode (always DC; left term identically 0)
    recs.add(_dec(64, f), 11)
    # coded_block_pattern (I_NxN only)
    lcl = _left(jnp.where(mb_i4, cbp4, jnp.where(cl16, 0xF, 0)))
    lcc = _left(cc)
    cbp_d = []
    for b in range(4):
        if b & 1:
            a_n = 1 - grp4[..., b - 1].astype(jnp.int32)
        else:
            a_n = jnp.where(col0, 0, 1 - ((lcl >> (b + 1)) & 1))
        b_n = (1 - grp4[..., b - 2].astype(jnp.int32)) if b & 2 \
            else jnp.zeros((nr, nc), jnp.int32)
        cbp_d.append(_dec(73 + a_n + 2 * b_n, grp4[..., b], mb_i4))
    recs.add(_cat(cbp_d[0], cbp_d[1]), 22)
    recs.add(_cat(cbp_d[2], cbp_d[3]), 22)
    d1 = _dec(77 + (lcc > 0).astype(jnp.int32), cc > 0, mb_i4)
    d2 = _dec(81 + (lcc == 2).astype(jnp.int32), cc == 2,
              mb_i4 & (cc > 0))
    recs.add(_cat(d1, d2), 22)
    # mb_qp_delta: I16 always codes it; I_NxN only when cbp nonzero
    recs.add(_dec(60, f, i16 | ((cbp4 > 0) | (cc > 0))), 11)
    # luma DC (cat 0, I16 only): left term requires a left I16 MB
    dcnz = luma_dc.any(-1).astype(jnp.int32)
    a = jnp.where(col0, 1, jnp.where(_left(i16), _left(dcnz), 0))
    v, ln, ov, mx = _residual_slots(luma_dc, 0, a + 2, i16)
    recs.add_batch(v, ln, mx)
    ovf = ov
    # luma blocks: I16 AC (cat 1, n=15) and I_NxN (cat 2, n=16) share a
    # 64-slot region per block (mutually exclusive per MB), both traced
    # once over the 16-block axis
    cbf_blk = jnp.where(mb_i4[..., None], i4nz, luma_ac.any(-1))
    incs = _luma_cbf_inc(_raster_grid(cbf_blk), left_skip, col0,
                         intra=True)
    v16, l16, ov16, _ = _residual_slots(
        luma_ac, 1, incs, (i16 & cl16)[..., None])
    v4, l4, ov4, mx4 = _residual_slots(
        luma_i4, 2, incs,
        mb_i4[..., None] & jnp.repeat(grp4, 4, axis=-1))
    padk = v4.shape[-1] - v16.shape[-1]               # cat1 is 4 short
    v16 = jnp.pad(v16, ((0, 0),) * 3 + ((0, padk),))
    l16 = jnp.pad(l16, ((0, 0),) * 3 + ((0, padk),))
    vm, lm = _merge((v16, l16), (v4, l4))
    recs.add_batch(vm.reshape(nr, nc, -1), lm.reshape(nr, nc, -1),
                   16 * mx4)
    ovf |= ov16.any(-1) | ov4.any(-1)
    # chroma residuals
    ovf |= _chroma_slots(recs, cb_dc, cb_ac, cr_dc, cr_ac, cc,
                         left_skip, col0, jnp.ones((nr, nc), bool),
                         intra=True)
    recs.add(_trm(jnp.broadcast_to(jnp.arange(nc) == nc - 1,
                                   (nr, nc))), 4)
    return _pack_stream(recs, ovf)


# ---------------------------------------------------------------------------
# Host-side helpers
# ---------------------------------------------------------------------------

def header_words(rows: int) -> int:
    return META_WORDS + rows


def payload_words(head: np.ndarray) -> int:
    return int(head[2])


def split_rows(buf: np.ndarray, rows: int):
    """Transport buffer (host array covering header + payload) ->
    (payload uint32, row_off int64 (rows+1,), row_bits int64) or None
    on the overflow flag."""
    head = buf[:META_WORDS + rows]
    assert int(head[0]) == 2, "cabac_binarize version mismatch"
    if int(head[1]):
        return None
    row_bits = head[META_WORDS:META_WORDS + rows].astype(np.int64)
    row_words = (row_bits + 31) >> 5
    row_off = np.zeros(rows + 1, np.int64)
    np.cumsum(row_words, out=row_off[1:])
    payload = np.ascontiguousarray(
        buf[META_WORDS + rows:META_WORDS + rows + int(row_off[-1])],
        dtype=np.uint32)
    return payload, row_off, row_bits


def stitch_rows(bufs, rows_each) -> np.ndarray:
    """Stitch per-shard transport buffers into one whole-frame buffer.

    Every cross-MB context in the record kernels above is a ``_left``
    shift WITHIN a row (slice-per-MB-row makes vertical neighbors
    unavailable), so a shard covering a contiguous block of MB rows
    emits exactly the rows a whole-frame binarize would — stitching is
    pure row concatenation: one header, the shards' per-row BIT tables
    back to back, then their word-aligned row payloads back to back.
    This is the L4 of the bitmerge hierarchy (slot -> block -> MB ->
    row -> FRAME), run on the host because the shards live on different
    chips.  The host engine replays the stitched buffer exactly as a
    single-device one (byte-identical AU; tests/test_spatial.py).

    ``bufs``: per-shard buffers in row order (each covering
    ``rows_each`` MB rows; an int or a per-shard sequence).  A shard's
    overflow flag poisons the stitched header (minimal flag-only
    buffer) so callers fall into the dense path without reading
    garbage row tables.
    """
    heads = [np.asarray(b) for b in bufs]
    if isinstance(rows_each, int):
        rows_each = [rows_each] * len(heads)
    total_rows = int(sum(rows_each))
    out_head = np.zeros(META_WORDS, np.uint32)
    out_head[0] = 2
    out_head[3] = total_rows
    out_head[4] = heads[0][4]
    if any(int(h[1]) for h in heads):
        out_head[1] = 1                      # overflow: flag-only
        return np.concatenate(
            [out_head, np.zeros(total_rows, np.uint32)])
    bit_tables, payloads = [], []
    total_words = 0
    for h, r in zip(heads, rows_each):
        assert int(h[0]) == 2, "cabac_binarize version mismatch"
        assert int(h[3]) == r, "shard row count disagrees with layout"
        row_bits = h[META_WORDS:META_WORDS + r]
        n = int(((row_bits.astype(np.int64) + 31) >> 5).sum())
        bit_tables.append(row_bits.astype(np.uint32))
        payloads.append(h[META_WORDS + r:META_WORDS + r + n]
                        .astype(np.uint32))
        total_words += n
    out_head[2] = total_words
    return np.concatenate([out_head] + bit_tables + payloads)


def decode_records_py(words: np.ndarray, nbits: int):
    """Decode one row's record stream into [(kind, ...), ...] — the
    pure-Python engine fallback and the wire-format test oracle.
    kinds: ("dec", ctx, b) ("run", ctx, cnt) ("byp", [bits]) ("trm", b).
    """
    out = []
    pos = 0

    def rd(n):
        nonlocal pos
        v = 0
        for _ in range(n):
            w = int(words[pos >> 5])
            v = (v << 1) | ((w >> (31 - (pos & 31))) & 1)
            pos += 1
        return v

    while pos < nbits:
        if rd(1) == 0:
            out.append(("dec", rd(9), rd(1)))
        elif rd(1) == 0:
            out.append(("run", rd(9), rd(4)))
        elif rd(1) == 0:
            n = rd(4)
            out.append(("byp", [rd(1) for _ in range(n)]))
        else:
            out.append(("trm", rd(1)))
    assert pos == nbits, "record stream over-ran its bit count"
    return out
