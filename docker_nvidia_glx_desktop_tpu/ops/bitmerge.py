"""Hierarchical variable-length bit concatenation on TPU — scatter-free.

``ops.bitpack.pack_bits`` concatenates codewords with a cumsum + scatter-OR.
That is the textbook formulation, but TPU scatter throughput is ~8M
elements/s (measured on v5e), so packing a 1080p
frame's ~7.5M codeword slots cost ~1 s — slower than the host entropy it
replaced.  This module rebuilds packing as *dense* VPU work with zero
scatters, exploiting the natural structure of a video bitstream:

  L1  slot -> block   each 4x4 block's <=34 codeword slots merge into a
                      fixed 8-word (256-bit) buffer by broadcast-compare
                      against the slot's cumsum bit offset (a dense mask
                      reduction — no scatter).
  L2  block -> MB     28 pieces (MB syntax + 27 blocks) merge into a
                      64-word (2048-bit) buffer the same dense way.
  L3  MB -> row       a binary reduction tree over 128 pieces (slice
                      header + 120 MBs + rbsp trailing + padding): each
                      level ORs the right piece into the left piece
                      shifted by the left piece's bit length, using a
                      logarithmic barrel shifter (static word shifts
                      selected per lane by the offset's binary digits).

Every stage is elementwise/broadcast arithmetic and no stage scatters,
but XLA does NOT fuse them into a handful of VPU kernels, as this text once
said: on the v5e a barrel-shifter stage (``pad`` + ``where`` over the whole
worst-case-sized buffer) is one round trip through HBM, a tree level is 4
to 17 of them, and whether the even/odd halves ``words[..., 0::2, :]`` are
slices or gathers hangs on the layout XLA picks for a minor dimension of 8
to 64.  The CAVLC programs paid 2.8 ms a 1080p frame (4.2 at 2560x1600) for
this hierarchy and the gather behind it (``pack_ms``); the CABAC record
packer, with 1,564 slots a macroblock, paid 23 ms (16 GB of passes to pack
under 1 MB).  On the TPU both now keep the same merge in VMEM: ONE packer,
``ops/cabac_pack.pack_rows`` (CABAC since PR 29, the CAVLC frame pack since
PR 31; PERF.md).  Off the TPU these functions are the packer of both
(``cavlc_device._pack_rows_bitmerge``, ``cabac_binarize._pack_rows_xla``),
of ``level_pack``, and the oracle the kernels are tested against.
Static caps (256 b/block, 2048 b/MB) bound the buffers;
content that overflows them (possible only near qp<=8 on pathological
blocks) raises a per-frame overflow flag and the caller falls back to host
entropy for that frame — correctness is never silently lost.

Word convention throughout: uint32, MSB-first bitstream order (bit 0 of
the stream is bit 31 of word 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_WORDS = 8           # 256-bit per-block buffer (L1 output)
MB_WORDS = 64             # 2048-bit per-MB buffer (L2 output)
BLOCK_CAP_BITS = 32 * BLOCK_WORDS
MB_CAP_BITS = 32 * MB_WORDS


def cumsum_mm(x, *, inclusive: bool = True):
    """Cumulative sum along the last (small) axis as a triangular matmul.

    XLA lowers ``jnp.cumsum`` on TPU to ``reduce_window`` — profiled at
    2.8 ms/frame for the (220k, 34) slot-offset cumsum alone.  A lower-
    triangular ones-matrix ``dot`` runs on the MXU in ~nothing.  Exact for
    the integer magnitudes used here (inputs <= 2^8, sums < 2^24: f32
    accumulation is lossless; HIGHEST precision keeps the operands f32).
    """
    n = x.shape[-1]
    # y[..., j] = sum_k x[..., k] * tri[k, j] with tri[k, j] = 1 iff k <= j
    # (k < j for the exclusive form): upper-triangular ones.
    tri = jnp.asarray(np.triu(np.ones((n, n), np.float32), 0 if inclusive
                              else 1))
    y = jax.lax.dot_general(
        x.astype(jnp.float32), tri, (((x.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return y.astype(x.dtype)


def _hi_lo(values, lengths, offsets):
    """Per-slot aligned word contributions (the pack_bits formulas).

    Returns (word_index, hi, lo): slot bits land in words ``w`` and
    ``w + 1`` with the given OR-patterns.
    """
    v = values.astype(jnp.uint32)
    ln = lengths.astype(jnp.int32)
    w = (offsets >> 5).astype(jnp.int32)
    s = (offsets & 31).astype(jnp.int32)
    end = s + ln
    straddle = end > 32
    sh_hi = jnp.where(straddle, end - 32, 32 - end)
    hi = jnp.where(straddle,
                   v >> sh_hi.astype(jnp.uint32),
                   v << jnp.clip(sh_hi, 0, 31).astype(jnp.uint32))
    hi = jnp.where(ln > 0, hi, 0)
    k = jnp.clip(end - 32, 0, 31)
    lo = jnp.where(straddle, v << (32 - k).astype(jnp.uint32), 0)
    return w, hi, lo


def slots_to_words(values, lengths, out_words: int):
    """Merge each row of <=S codeword slots into a fixed word buffer.

    values/lengths: (..., S).  Returns (words (..., out_words) uint32,
    nbits (...,) int32, overflow (...,) bool).  Dense mask reduction:
    cost S * out_words * 2 multiply-selects per row — no scatter.
    """
    ln = lengths.astype(jnp.int32)
    offsets = cumsum_mm(ln, inclusive=False)
    nbits = offsets[..., -1] + ln[..., -1]
    w, hi, lo = _hi_lo(values, lengths, offsets)

    wi = jnp.arange(out_words, dtype=jnp.int32)
    shape = w.shape + (1,)
    # (..., S, out_words) broadcast-compare, reduced over S.
    words = (jnp.where(w.reshape(shape) == wi, hi[..., None], 0).sum(-2)
             + jnp.where((w + 1).reshape(shape) == wi, lo[..., None], 0).sum(-2))
    return words.astype(jnp.uint32), nbits, nbits > 32 * out_words


def merge_pieces_dense(words, nbits, out_words: int):
    """Concatenate P variable-length word buffers along axis -2, densely.

    words: (..., P, Win), nbits: (..., P).  Returns (out (..., out_words),
    total_bits, overflow).  Cost P * Win * out_words selects per row —
    right for small P*Win (the L2 block->MB merge).
    """
    nbits = nbits.astype(jnp.int32)
    off = cumsum_mm(nbits, inclusive=False)           # (..., P)
    total = off[..., -1] + nbits[..., -1]
    k = (off >> 5)[..., None]                          # (..., P, 1)
    s = (off & 31)[..., None]
    win = words.shape[-1]
    su = s.astype(jnp.uint32)
    hi = words >> su                                   # (..., P, Win)
    lo = jnp.where(s == 0, 0, words << (32 - su))
    wi = jnp.arange(out_words, dtype=jnp.int32)        # (out,)
    ji = jnp.arange(win, dtype=jnp.int32)              # (Win,)
    # piece word j lands at out words k+j (hi part) and k+j+1 (lo part)
    tgt = k + ji[..., None, :]                         # (..., P, Win)
    m_hi = tgt[..., None] == wi                        # (..., P, Win, out)
    m_lo = (tgt + 1)[..., None] == wi
    out = (jnp.where(m_hi, hi[..., None], 0).sum((-3, -2))
           + jnp.where(m_lo, lo[..., None], 0).sum((-3, -2)))
    return out.astype(jnp.uint32), total, total > 32 * out_words


def _shift_right_bits(arr, shift_bits):
    """Shift each row of a word buffer right by a dynamic bit count.

    arr: (..., W) uint32; shift_bits: (...,) int32 in [0, 32*W).
    Logarithmic barrel shifter: one static word-roll per offset bit plus a
    single sub-word bit pass — all elementwise selects, no gathers.
    """
    w = arr.shape[-1]
    k = (shift_bits >> 5).astype(jnp.int32)
    s = (shift_bits & 31).astype(jnp.int32)
    n_stages = max(1, int(np.ceil(np.log2(max(w, 2)))))
    for t in range(n_stages):
        step = 1 << t
        if step >= w:
            break
        shifted = jnp.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(step, 0)])[..., :w]
        arr = jnp.where(((k >> t) & 1)[..., None] == 1, shifted, arr)
    su = s.astype(jnp.uint32)[..., None]
    prev = jnp.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(1, 0)])[..., :w]
    lo = jnp.where(s[..., None] == 0, 0, prev << (32 - su))
    return jnp.where(s[..., None] == 0, arr, (arr >> su) | lo)


def merge_pieces_tree(words, nbits):
    """Concatenate P (power of two) variable-length pieces via a binary
    reduction tree of barrel-shifted ORs.

    words: (..., P, W), nbits: (..., P).  Returns (out (..., P*W), total).
    Each level pairs pieces (A, B) -> A | (B >> len(A)) over doubled
    buffers; cost O(P * W * log(P*W)) elementwise ops per row.
    """
    p = words.shape[-2]
    assert p & (p - 1) == 0, "piece count must be a power of two"
    nbits = nbits.astype(jnp.int32)
    while p > 1:
        a = words[..., 0::2, :]
        b = words[..., 1::2, :]
        la = nbits[..., 0::2]
        lb = nbits[..., 1::2]
        w = a.shape[-1]
        a2 = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w)])
        b2 = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, w)])
        words = a2 | _shift_right_bits(b2, la)
        nbits = la + lb
        p //= 2
    return words[..., 0, :], nbits[..., 0]
