"""The bit packer of the TPU: two Pallas kernels whose merge stages stay
in VMEM.

``cabac_binarize._pack_stream`` turns (R, C, S) record slots into the
version-2 transport buffer (``pack_rows``), ``cavlc_device.pack_frame`` CAVLC
slots into the rows of ``flat`` (``pack_rows_slot_major``: its builder codes
its blocks block-major and hands over (S, R * C) slot words, kernel A's own
order, PR 45).  Their XLA form (``bitmerge``'s dense L1
and merge trees) is right everywhere and stays the CPU path and the oracle,
but on the chip every barrel-shifter stage of those trees is a round trip
of the whole worst-case-sized buffer through HBM: 16 GB of passes to pack
under 1 MB of records (PERF.md, PR 29), and for CAVLC a row tree that
doubles at 163 pieces plus a 131,072-word gather whatever the picture
(PR 31).  Here the same bits are put in the same places by

  kernel A, slot -> macroblock   1,024 macroblocks on the (8, 128) lanes of
      a vreg, the word axis on the MAJOR axis.  Every 8 slots merge into
      an 8-word piece by broadcast-compare (``bitmerge``'s L1, in
      registers), already shifted by the macroblock's bit phase in its
      row.  The pieces are then compacted by a compress network: a word's
      way to its place is ``d`` words, ``d`` rises with the source
      position, so moving by ``d``'s bits, lowest first, never makes two
      words meet unless they share a destination, and then they are OR-ed
      (they hold different bits of it).  A move by 2^t words is a move
      along the major axis chosen per lane: no lane shuffle, no gather,
      11 stages over 1,568 words, in place.
  kernel B, macroblock -> row -> frame   walks the macroblocks in stream
      order, their word offsets SCALARS.  A macroblock of up to 127 words
      (CAVLC's 65) is one line of 128, eight macroblocks a chunk: one roll
      a macroblock, two chunks put together in registers and OR-ed into
      the row's buffer in VMEM.  A longer one (a CABAC P macroblock's
      1,024 words, an I macroblock's two chunks) is whole (8, 128) chunks:
      three rolls put a chunk at its offset, two ORs put it into the
      buffer.  The chunks a row touched go to the payload by DMA at the
      row's word offset.  Work follows the content, not the cap.

XLA does what is left: packing (value, length) into one word, the bit
counts and their prefix sums, two 2-D transposes (one where the slots come
slot-major), the header.  R, C, S and
the piece size follow from the shapes and the caps: one algorithm for both
entropy coders, the ring, the shards and the masked path's worklist.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 1024               # macroblocks a kernel-A step: one (8, 128) vreg
CHUNK = 1024              # words a kernel-B chunk: one (8, 128) vreg
_LEN_SHIFT = 26           # a slot is at most 26 bits: value | length << 26
_DMA_CHUNKS = 8           # kernel B writes the payload 8 chunks at a time
_SLAB = 8                 # positions a step of kernel A's network moves
# kernel A holds a tile's slots (twice: the pipeline's two buffers), its
# words (twice) and their distances; 35 MB for an I picture's 1,672 slots.
# The v5e's VMEM is 128 MiB, the compiler's default scoped limit 16 MiB.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024

_srl = jax.lax.shift_right_logical
_shl = jax.lax.shift_left


def _compact_kernel(sh_ref, slots_ref, out_ref, w_ref, dd_ref, *, n_groups):
    """Kernel A: one tile's slots -> each macroblock's words, compacted.

    sh_ref (8, 128): bit phase of each macroblock's first bit in its word.
    slots_ref (8 * n_groups, 8, 128): value | length << 26, slot-major.
    w_ref (scratch) the same shape: word w of every macroblock; dd_ref
    (scratch): how far each word still has to go.  out_ref: the first words
    of w_ref, as many as a macroblock's piece may hold."""
    n_words = 8 * n_groups

    def group(g, carry):
        """Slots 8g..8g+7 -> words 8g..8g+7, as ``bitmerge._hi_lo`` and
        ``slots_to_words`` place them; ``bit``: the stream's length so far
        (with the phase).  Eight slots of up to 32 bits behind a phase of
        up to 31 reach into a ninth word: ``spill``, which is word 0 of
        the next group (the same place and the same way to go: the group
        then ends in the word its successor starts in)."""
        bit, spill = carry
        base = g * 8
        slab = slots_ref[pl.ds(base, 8)]
        off = bit & 31
        words = [spill] + [jnp.zeros((8, 128), jnp.int32)] * 7
        spill = jnp.zeros((8, 128), jnp.int32)
        for j in range(8):
            x = slab[j]
            ln = _srl(x, _LEN_SHIFT)
            val = x & ((1 << _LEN_SHIFT) - 1)
            w = off >> 5
            end = (off & 31) + ln
            straddle = end > 32
            hi = jnp.where(straddle, _srl(val, (end - 32) & 31),
                           _shl(val, (32 - end) & 31))
            lo = jnp.where(straddle, _shl(val, (64 - end) & 31), 0)
            at = [w == k for k in range(8)]
            for k in range(8):
                words[k] = words[k] | jnp.where(at[k], hi, 0)
                if k:
                    words[k] = words[k] | jnp.where(at[k - 1], lo, 0)
            spill = spill | jnp.where(at[7], lo, 0)
            off = off + ln
        words = jnp.stack(words)
        w_ref[pl.ds(base, 8)] = words
        dd_ref[pl.ds(base, 8)] = jnp.where(words != 0,
                                           base - (bit >> 5), 0)
        return bit + off - (bit & 31), spill

    # (``pack_rows`` flags a frame whose last group could spill)
    jax.lax.fori_loop(0, n_groups, group,
                      (sh_ref[...], jnp.zeros((8, 128), jnp.int32)))

    # The compress network, in place and upwards: position p takes what it
    # keeps and what comes down from p + step; a slab of positions reads
    # both before it writes, and the slabs after it have not been written.
    def move(p, size, step):
        here, there = pl.ds(p, size), pl.ds(p + step, size)
        x0, e0 = w_ref[here], dd_ref[here]
        x1, e1 = w_ref[there], dd_ref[there]
        keep = (e0 & step) == 0
        come = (e1 & step) != 0
        w_ref[here] = jnp.where(keep, x0, 0) | jnp.where(come, x1, 0)
        dd_ref[here] = jnp.where(keep, e0, 0) | jnp.where(come, e1, 0)

    def leave(p, size, step):
        here = pl.ds(p, size)
        e0 = dd_ref[here]
        keep = (e0 & step) == 0
        w_ref[here] = jnp.where(keep, w_ref[here], 0)
        dd_ref[here] = jnp.where(keep, e0, 0)

    def sweep(fn, lo, hi, step):
        """``fn`` over positions lo..hi-1 (static), a slab at a time."""
        n_slabs, rest = divmod(hi - lo, _SLAB)

        def body(i, _):
            fn(lo + i * _SLAB, _SLAB, step)
            return _

        jax.lax.fori_loop(0, n_slabs, body, 0)
        if rest:
            fn(lo + n_slabs * _SLAB, rest, step)

    step = 1
    while step < n_words:
        sweep(move, 0, n_words - step, step)
        sweep(leave, n_words - step, n_words, step)
        step *= 2

    def hand_out(p, size, _):
        out_ref[pl.ds(p, size)] = w_ref[pl.ds(p, size)]

    sweep(hand_out, 0, out_ref.shape[0], None)


def _rows_kernel(gw_ref, fc_ref, pieces_ref, _zeros_ref, out_ref,
                 rowbuf, carry, sem, *, cols, piece_chunks):
    """Kernel B: one MB row's pieces -> its stretch of the payload.

    gw_ref SMEM: the payload word each macroblock starts in.  fc_ref
    (R + 1,) SMEM: the payload chunk each row starts in (and the chunk the
    last row ends in).  pieces_ref: the row's macroblocks, each already at
    its bit phase: (cols, piece_chunks, 8, 128), a macroblock whole chunks,
    or with ``piece_chunks`` 0 (cols / 8, 8, 128), a macroblock one line of
    128 words and eight macroblocks a chunk (gw_ref is then padded to the
    same eights).  out_ref: the payload in HBM as chunks, zero where no row
    writes."""
    r = pl.program_id(0)
    first = fc_ref[r]
    n_chunks = fc_ref[r + 1] - first + 1
    n_dma = (n_chunks + _DMA_CHUNKS - 1) // _DMA_CHUNKS

    def clear(i, _):
        rowbuf[i] = jnp.zeros((8, 128), jnp.int32)
        return _

    jax.lax.fori_loop(0, n_dma * _DMA_CHUNKS + max(piece_chunks, 1), clear, 0)

    # the chunk this row starts in holds the end of the rows before it
    @pl.when(r > 0)
    def _():
        rowbuf[0] = carry[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lin = sub * 128 + lane

    def place(c, _):
        k = gw_ref[r * cols + c] - first * CHUNK
        q = k >> 10
        o = k & (CHUNK - 1)
        a = o >> 7
        b = o & 127
        for j in range(piece_chunks):
            v = pieces_ref[c, j]
            # the chunk as a line of 1,024 words, turned by ``o``
            t = pltpu.roll(v, b, 1)
            t = jnp.where(lane >= b, t, pltpu.roll(t, 1, 0))
            t = pltpu.roll(t, a, 0)
            rowbuf[q + j] = rowbuf[q + j] | jnp.where(lin >= o, t, 0)
            rowbuf[q + j + 1] = rowbuf[q + j + 1] | jnp.where(lin < o, t, 0)
        return _

    def place_lines(g, _):
        """Eight macroblocks of a line each: under 1,024 words in all, so
        they lie in the chunk the first starts in and the one behind it,
        and both are put together in registers."""
        at = (r * (cols // 8) + g) * 8
        q = (gw_ref[at] - first * CHUNK) >> 10
        lines = pieces_ref[g]
        here = jnp.zeros((8, 128), jnp.int32)
        behind = jnp.zeros((8, 128), jnp.int32)
        for m in range(8):
            k = gw_ref[at + m] - (first + q) * CHUNK      # 0 .. 2,047
            a = k >> 7
            b = k & 127
            # the line turned by ``b``: what passes lane 127 belongs to
            # the line below
            t = pltpu.roll(jnp.broadcast_to(lines[m:m + 1], (8, 128)), b, 1)
            row = jnp.where(lane >= b, a, a + 1)
            here = here | jnp.where(sub == row, t, 0)
            behind = behind | jnp.where(sub == row - 8, t, 0)
        rowbuf[q] = rowbuf[q] | here
        rowbuf[q + 1] = rowbuf[q + 1] | behind
        return _

    if piece_chunks:
        jax.lax.fori_loop(0, cols, place, 0)
    else:
        jax.lax.fori_loop(0, cols // 8, place_lines, 0)
    carry[...] = rowbuf[n_chunks - 1]

    def copy(i):
        return pltpu.make_async_copy(
            rowbuf.at[pl.ds(i * _DMA_CHUNKS, _DMA_CHUNKS)],
            out_ref.at[pl.ds(first + i * _DMA_CHUNKS, _DMA_CHUNKS)], sem)

    def start(i, _):
        copy(i).start()
        return _

    def wait(i, _):
        copy(i).wait()
        return _

    jax.lax.fori_loop(0, n_dma, start, 0)
    jax.lax.fori_loop(0, n_dma, wait, 0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def slot_words(vals, lns):
    """(value, length) -> one ``int32`` a slot, value | length << 26: what
    kernel A reads.  The value of an empty slot is dropped."""
    packed = (jnp.where(lns > 0,
                        vals.astype(jnp.uint32)
                        & ((1 << _LEN_SHIFT) - 1), 0)
              | (lns.astype(jnp.uint32) << _LEN_SHIFT))
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def pack_rows(vals, lns, value_ovf, mb_cap: int, out_words: int):
    """(R, C, S) slots -> (overflow flag, per-row bit counts, payload of
    ``out_words`` words): every row's bits from a word of its own, the rows
    one behind the other, zeros behind the last; word for word what the
    bitmerge hierarchy gives (``cabac_binarize._pack_rows_xla``,
    ``cavlc_device._pack_rows_bitmerge``).  A slot is a value of at most
    26 bits and a length of at most 32 (the bits above the value are
    zeros, as a level escape's prefix is); ``value_ovf`` (R, C) is what
    the caller found too long by its own caps; ``mb_cap`` words a
    macroblock and ``out_words`` in all are the static caps.  Everything
    else follows from the shapes."""
    return _pack_rows(vals, lns, value_ovf, mb_cap, out_words, 2)


def pack_rows_slot_major(slots, value_ovf, mb_cap: int, out_words: int):
    """``pack_rows`` for a caller that builds its slots as kernel A reads
    them: ``slot_words`` of shape (S, R * C), a slot of every macroblock
    one lane-dense row (the CAVLC slot builder's block-major order,
    ``cavlc_device.pack_frame``).  R and C are ``value_ovf``'s; the same
    words."""
    return _pack_rows(slots, None, value_ovf, mb_cap, out_words, 0)


def _pack_rows(vals, lns, value_ovf, mb_cap: int, out_words: int,
               slot_axis: int):
    """The packer behind both ways in.  ``slot_axis`` 2: ``vals`` and
    ``lns`` (R, C, S), packed and turned here; 0: ``vals`` the slot words
    (S, R * C), their lengths read off them where they are summed."""
    r, c = value_ovf.shape
    s = vals.shape[slot_axis]
    s8 = _round_up(s, 8)
    n_groups = s8 // 8
    n = r * c
    n_pad = _round_up(n, TILE)
    # words a macroblock's piece may hold, with its phase: one line of a
    # chunk (eight macroblocks a chunk: CAVLC's 65) or whole chunks
    lines = mb_cap + 1 <= 128
    piece_chunks = 0 if lines else -(-(mb_cap + 1) // CHUNK)
    piece_words = 128 if lines else piece_chunks * CHUNK
    c8 = _round_up(c, 8) if lines else c

    with jax.named_scope("cabac_offsets"):
        if slot_axis:
            lns = lns.astype(jnp.int32)
            bits = lambda part: part
        else:       # the lengths lie in the slot words: read where summed
            lns = vals
            bits = lambda words: _srl(words, _LEN_SHIFT)
        mb_bits = bits(lns).sum(slot_axis).reshape(r, c)
        row_bits = mb_bits.sum(-1)
        total_words = ((row_bits + 31) >> 5).sum()
        # (kernel A drops what the last eight slots spill behind word S)
        last_group = [(s8 - 8) * (ax == slot_axis) for ax in range(lns.ndim)]
        overflow = (value_ovf.any() | (mb_bits > 32 * mb_cap).any()
                    | (total_words > out_words)
                    | (bits(jax.lax.slice(lns, last_group, lns.shape))
                       .sum(slot_axis) > 32 * 8 - 31).any())
        # an overflowing frame is coded again by the dense path: its
        # buffer only has to carry the flag, and nothing may leave the
        # row buffers
        live = jnp.where(overflow, 0, mb_bits)
        in_row = jnp.cumsum(live, axis=-1) - live               # (R, C)
        row_words = (live.sum(-1) + 31) >> 5
        offs = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(row_words)])   # (R + 1,)
        gw = (offs[:r, None] + (in_row >> 5)).reshape(n)
        fc = offs >> 10
        phase = jnp.pad((in_row & 31).reshape(n), (0, n_pad - n))

    with jax.named_scope("cabac_slots_major"):
        if slot_axis:
            packed = jnp.pad(slot_words(vals, lns).reshape(n, s),
                             ((0, n_pad - n), (0, s8 - s))).T
        else:
            packed = jnp.pad(vals, ((0, s8 - s), (0, n_pad - n)))
        slots = packed.reshape(s8, n_pad // 128, 128)

    keep = min(s8, piece_words)
    with jax.named_scope("cabac_compact"):
        words = pl.pallas_call(
            functools.partial(_compact_kernel, n_groups=n_groups),
            name="cabac_compact",
            out_shape=jax.ShapeDtypeStruct((keep,) + slots.shape[1:],
                                           jnp.int32),
            grid=(n_pad // TILE,),
            in_specs=[pl.BlockSpec((8, 128), lambda t: (t, 0)),
                      pl.BlockSpec((s8, 8, 128), lambda t: (0, t, 0))],
            out_specs=pl.BlockSpec((keep, 8, 128), lambda t: (0, t, 0)),
            scratch_shapes=[pltpu.VMEM((s8, 8, 128), jnp.int32)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
        )(phase.reshape(n_pad // 128, 128), slots)

    with jax.named_scope("cabac_mb_major"):
        words = jnp.pad(words.reshape(keep, n_pad),
                        ((0, piece_words - keep), (0, 0)))
        if lines:
            # a row's macroblocks from a chunk of their own, in eights
            pieces = jnp.pad(words.T[:n].reshape(r, c, 128),
                             ((0, 0), (0, c8 - c), (0, 0)))
            pieces = pieces.reshape(r, c8 // 8, 8, 128)
            gw = jnp.pad(gw.reshape(r, c), ((0, 0), (0, c8 - c)),
                         mode="edge").reshape(r * c8)
            block = (None, c8 // 8, 8, 128)
        else:
            pieces = words.T.reshape(n_pad, piece_chunks, 8, 128)
            block = (c, piece_chunks, 8, 128)

    # a row touches the chunks of its own words and, with the piece that
    # ends it, one more; the DMA writes whole groups of chunks
    row_chunks = _round_up(-(-c * mb_cap // CHUNK) + 1, _DMA_CHUNKS)
    n_out = -(-out_words // CHUNK) + row_chunks
    with jax.named_scope("cabac_rows"):
        payload = pl.pallas_call(
            functools.partial(_rows_kernel, cols=c8,
                              piece_chunks=piece_chunks),
            name="cabac_rows",
            out_shape=jax.ShapeDtypeStruct((n_out, 8, 128), jnp.int32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(r,),
                in_specs=[
                    pl.BlockSpec(block, lambda i, gw, fc: (i, 0, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[
                    pltpu.VMEM((row_chunks + piece_chunks + 1, 8, 128),
                               jnp.int32),
                    pltpu.VMEM((8, 128), jnp.int32),
                    pltpu.SemaphoreType.DMA(())]),
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )(gw, fc, pieces, jnp.zeros((n_out, 8, 128), jnp.int32))

    payload = jax.lax.bitcast_convert_type(payload, jnp.uint32)
    return overflow, row_bits, payload.reshape(-1)[:out_words]
