"""Parallel variable-length bit packing on TPU.

Entropy coding is nominally sequential — the classic argument for keeping it
on the host (SURVEY.md §7 hard part #1).  But *given* the codes, concatenating
variable-length codewords is a scan: an exclusive cumsum of code lengths gives
every codeword its absolute bit offset, and because the bit ranges are
disjoint, scatter-ADD into 32-bit words is equivalent to scatter-OR.  That
turns Huffman/VLC packing into two vectorized passes that XLA maps onto the
VPU, leaving only byte stuffing (and for H.264, emulation prevention) on the
host over the ~100x smaller packed output.

This matters doubly here: the host<->device link is the scarce resource (on
a TPU VM PCIe is ~10 GB/s, but a 4K60 stream still wants the 30x
reduction), so the bitstream — not
the coefficient tensor — is what crosses the link.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_bits(values, lengths):
    """Concatenate variable-length codewords into a big-endian bit stream.

    values:  (N,) uint32 — right-aligned bit patterns (the codeword in the
             low ``lengths[i]`` bits; higher bits must be zero).
    lengths: (N,) int32 in [0, 32] — zero-length entries contribute nothing.

    Returns (packed_bytes, total_bits):
      packed_bytes: (ceil(maxbits/8),) uint8 device array, MSB-first; only
                    the first ceil(total_bits/8) bytes are meaningful and
                    trailing unused bits are 0.
      total_bits:   scalar int32 device array.
    """
    v = jnp.asarray(values, jnp.uint32)
    ln = jnp.asarray(lengths, jnp.int32)

    offsets = jnp.cumsum(ln) - ln                 # exclusive cumsum
    total_bits = offsets[-1] + ln[-1] if ln.shape[0] else jnp.int32(0)

    w = (offsets >> 5).astype(jnp.int32)          # word index
    s = (offsets & 31).astype(jnp.int32)          # bit offset in word
    end = s + ln                                   # in (0, 64]
    straddle = end > 32

    # High word: top bits of the codeword aligned at bit s.
    sh_hi = jnp.where(straddle, end - 32, 32 - end)
    hi = jnp.where(straddle,
                   v >> sh_hi.astype(jnp.uint32),
                   v << jnp.clip(sh_hi, 0, 31).astype(jnp.uint32))
    hi = jnp.where(ln > 0, hi, 0)

    # Low word: remaining (end - 32) bits, MSB-aligned.
    k = jnp.clip(end - 32, 0, 31)                 # bits in second word
    lo = (v << jnp.clip(32 - k, 0, 31).astype(jnp.uint32))
    lo = jnp.where(straddle, lo, 0)

    # Each entry is <= 32 bits, so N words + 1 (straddle spill) always fit.
    nwords = int(v.shape[0]) + 1
    words = jnp.zeros(nwords, jnp.uint32)
    words = words.at[w].add(hi, mode="drop")
    words = words.at[w + 1].add(lo, mode="drop")

    by = jnp.stack([(words >> 24) & 0xFF, (words >> 16) & 0xFF,
                    (words >> 8) & 0xFF, words & 0xFF], axis=-1)
    packed = by.reshape(-1).astype(jnp.uint8)
    return packed, total_bits


def finalize_bytes(packed_bytes, total_bits, pad_bit: int = 1) -> bytes:
    """Host-side: trim to total_bits, pad the final partial byte.

    ``packed_bytes``/``total_bits`` may be device arrays; this is the one
    host pull of the entropy stage.
    """
    import numpy as np
    nbits = int(total_bits)
    nbytes = (nbits + 7) // 8
    data = np.asarray(packed_bytes[:nbytes]).copy()
    rem = nbits % 8
    if rem and pad_bit:
        data[-1] |= (1 << (8 - rem)) - 1
    return data.tobytes()


def jpeg_stuff_bytes(data: bytes) -> bytes:
    """Insert 0x00 after every 0xFF (T.81 §B.1.1.5), vectorized on host."""
    import numpy as np
    arr = np.frombuffer(data, np.uint8)
    pos = np.nonzero(arr == 0xFF)[0]
    if len(pos) == 0:
        return data
    return np.insert(arr, pos + 1, 0).tobytes()
