"""Quantization: JPEG Annex-K tables with libjpeg quality scaling, and the
H.264 integer quant/dequant (MF/V) machinery.

On TPU quantization is elementwise multiply + shift over the blocked
coefficient tensor — pure VPU work that XLA fuses with the preceding
transform.  The H.264 path reproduces the JM/x264 fixed-point formulation:

    level  = sign(w) * ((|w| * MF[qp%6] + f) >> qbits),   qbits = 15 + qp//6
    w'     = level * V[qp%6] << (qp//6)                    (AC dequant)

so reconstruction matches conformant decoders exactly.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# JPEG (ITU T.81 Annex K) base tables + libjpeg quality scaling
# ---------------------------------------------------------------------------

JPEG_LUMA_Q = np.array(
    [
        [16, 11, 10, 16, 24, 40, 51, 61],
        [12, 12, 14, 19, 26, 58, 60, 55],
        [14, 13, 16, 24, 40, 57, 69, 56],
        [14, 17, 22, 29, 51, 87, 80, 62],
        [18, 22, 37, 56, 68, 109, 103, 77],
        [24, 35, 55, 64, 81, 104, 113, 92],
        [49, 64, 78, 87, 103, 121, 120, 101],
        [72, 92, 95, 98, 112, 100, 103, 99],
    ],
    dtype=np.int32,
)

JPEG_CHROMA_Q = np.array(
    [
        [17, 18, 24, 47, 99, 99, 99, 99],
        [18, 21, 26, 66, 99, 99, 99, 99],
        [24, 26, 56, 99, 99, 99, 99, 99],
        [47, 66, 99, 99, 99, 99, 99, 99],
        [99, 99, 99, 99, 99, 99, 99, 99],
        [99, 99, 99, 99, 99, 99, 99, 99],
        [99, 99, 99, 99, 99, 99, 99, 99],
        [99, 99, 99, 99, 99, 99, 99, 99],
    ],
    dtype=np.int32,
)


def jpeg_quality_tables(quality: int):
    """libjpeg-style quality (1..100) scaling of the Annex-K tables."""
    quality = int(np.clip(quality, 1, 100))
    scale = 5000 // quality if quality < 50 else 200 - quality * 2
    luma = np.clip((JPEG_LUMA_Q * scale + 50) // 100, 1, 255).astype(np.int32)
    chroma = np.clip((JPEG_CHROMA_Q * scale + 50) // 100, 1, 255).astype(np.int32)
    return luma, chroma


def jpeg_quantize(coefs, table):
    """Round-to-nearest divide of DCT coefficients by the quant table."""
    t = jnp.asarray(table, jnp.float32)
    return jnp.round(jnp.asarray(coefs, jnp.float32) / t).astype(jnp.int32)


def jpeg_dequantize(levels, table):
    return jnp.asarray(levels, jnp.int32) * jnp.asarray(table, jnp.int32)


# ---------------------------------------------------------------------------
# H.264 quant (JM/x264 fixed-point; spec §8.5)
# ---------------------------------------------------------------------------

# MF (multiplication factor) per qp%6, by coefficient position class:
#   a: (0,0),(0,2),(2,0),(2,2)   b: (1,1),(1,3),(3,1),(3,3)   c: others
_MF_A = np.array([13107, 11916, 10082, 9362, 8192, 7282], dtype=np.int32)
_MF_B = np.array([5243, 4660, 4194, 3647, 3355, 2893], dtype=np.int32)
_MF_C = np.array([8066, 7490, 6554, 5825, 5243, 4559], dtype=np.int32)

# V (dequant scale) per qp%6, same position classes.
_V_A = np.array([10, 11, 13, 14, 16, 18], dtype=np.int32)
_V_B = np.array([16, 18, 20, 23, 25, 29], dtype=np.int32)
_V_C = np.array([13, 14, 16, 18, 20, 23], dtype=np.int32)


def _position_table(vec_a, vec_b, vec_c, dtype):
    """Build (6, 4, 4) tables from the three position-class vectors."""
    out = np.empty((6, 4, 4), dtype=dtype)
    for r in range(6):
        for i in range(4):
            for j in range(4):
                if (i % 2 == 0) and (j % 2 == 0):
                    out[r, i, j] = vec_a[r]
                elif (i % 2 == 1) and (j % 2 == 1):
                    out[r, i, j] = vec_b[r]
                else:
                    out[r, i, j] = vec_c[r]
    return out


MF_TABLE = _position_table(_MF_A, _MF_B, _MF_C, np.int32)   # (6,4,4)
V_TABLE = _position_table(_V_A, _V_B, _V_C, np.int32)       # (6,4,4)

# Chroma QP mapping for QPy 30..51 (below 30, QPc == QPy).  Spec Table 8-15.
_QPC_HIGH = np.array(
    [29, 30, 31, 32, 32, 33, 34, 34, 35, 35, 36, 36, 37, 37, 37, 38, 38, 38, 39, 39, 39, 39],
    dtype=np.int32,
)

# Full 0..51 chroma-QP table (offset 0) for the vector (per-MB qp) path.
QPC_TABLE = np.array(
    [q if q < 30 else int(_QPC_HIGH[q - 30]) for q in range(52)],
    dtype=np.int32)


def chroma_qp(qp_y: int, chroma_qp_index_offset: int = 0) -> int:
    q = int(np.clip(qp_y + chroma_qp_index_offset, 0, 51))
    return int(q) if q < 30 else int(_QPC_HIGH[q - 30])


def chroma_qp_v(qp_y):
    """Vector chroma QP: per-MB int32 array in, Table 8-15 mapped out."""
    q = jnp.clip(jnp.asarray(qp_y, jnp.int32), 0, 51)
    return jnp.asarray(QPC_TABLE)[q]


def chroma_qp_any(qp_y):
    """Table 8-15 for either kind of qp: a Python int stays a Python
    int (the compile-time-constant path), a traced scalar or per-MB
    array goes through the table gather."""
    return chroma_qp(qp_y) if _is_static_qp(qp_y) else chroma_qp_v(qp_y)


#: The tunes whose stages take the slice qp as a TRACED scalar (one compiled
#: program for every rung of the rate ladder): "off", and "hq", where
#: everything downstream of the slice qp is the (R, C) plane ``aq.qp_plane``
#: makes of it (quantizers, chroma qp and lambdas are per macroblock
#: already).  ``hq_noaq`` is not among them: its lambdas are compile-time
#: floats.
TRACED_QP_TUNES = ("off", "hq")


def require_static_qp_for(qp, tune: str) -> None:
    """A Python qp for every tune outside :data:`TRACED_QP_TUNES`."""
    if tune not in TRACED_QP_TUNES and not _is_static_qp(qp):
        raise TypeError(f"tune={tune!r} needs a static (Python int) qp")


def _is_static_qp(qp) -> bool:
    """True for a Python/numpy scalar qp (the compile-time-constant path
    every pre-tune caller uses; kept byte-for-byte identical).  Traced
    arrays take the vector (per-MB) path below."""
    return isinstance(qp, (int, np.integer))


def _vq(qp, coefs_ndim: int, block_dims: int = 2):
    """Broadcast a per-MB qp array against coefficient leading dims:
    qp (...,) -> (..., 1, 1) aligned under ``block_dims`` trailing block
    axes.  The qp array must be broadcastable to coefs.shape[:-block_dims]."""
    q = jnp.asarray(qp, jnp.int32)
    extra = coefs_ndim - q.ndim - block_dims
    q = q.reshape(q.shape + (1,) * (block_dims + max(extra, 0)))
    return q


def h264_quantize_4x4(coefs, qp, intra: bool = True):
    """Quantize core-transform coefficients, trailing dims (4, 4).

    ``qp`` is either a static int (one compiled table constant — the
    pre-tune path, unchanged) or a per-MB int32 array broadcastable to
    the leading dims (the ENCODER_TUNE=hq adaptive-quantization path)."""
    w = jnp.asarray(coefs, jnp.int32)
    if _is_static_qp(qp):
        qbits = 15 + qp // 6
        mf = jnp.asarray(MF_TABLE[qp % 6])
        f = (1 << qbits) // 3 if intra else (1 << qbits) // 6
        level = (jnp.abs(w) * mf + f) >> qbits
        return (jnp.sign(w) * level).astype(jnp.int32)
    q = _vq(qp, w.ndim)
    qbits = 15 + q // 6
    mf = jnp.asarray(MF_TABLE)[(q % 6)[..., 0, 0]]   # (..., 4, 4) pos table
    f = jnp.left_shift(1, qbits) // (3 if intra else 6)
    level = (jnp.abs(w) * mf + f) >> qbits
    return (jnp.sign(w) * level).astype(jnp.int32)


def h264_dequantize_4x4(levels, qp):
    """Dequantize 4x4 AC levels per spec §8.5.12.1 (no rounding)."""
    lv = jnp.asarray(levels, jnp.int32)
    if _is_static_qp(qp):
        v = jnp.asarray(V_TABLE[qp % 6])
        return (lv * v) << (qp // 6)
    q = _vq(qp, lv.ndim)
    v = jnp.asarray(V_TABLE)[(q % 6)[..., 0, 0]]
    return (lv * v) << (q // 6)


def h264_quantize_luma_dc(dc_hadamard, qp):
    """Quantize the 4x4 Hadamard-transformed luma DC block (JM convention).

    Uses MF[qp%6][0,0] with an extra >>1 of headroom: qbits + 1.
    """
    w = jnp.asarray(dc_hadamard, jnp.int32)
    if _is_static_qp(qp):
        qbits = 15 + qp // 6
        mf00 = int(MF_TABLE[qp % 6][0, 0])
        f = (1 << qbits) // 3
        level = (jnp.abs(w) * mf00 + 2 * f) >> (qbits + 1)
        return (jnp.sign(w) * level).astype(jnp.int32)
    q = _vq(qp, w.ndim)
    qbits = 15 + q // 6
    mf00 = jnp.asarray(_MF_A)[q % 6]
    f = jnp.left_shift(1, qbits) // 3
    level = (jnp.abs(w) * mf00 + 2 * f) >> (qbits + 1)
    return (jnp.sign(w) * level).astype(jnp.int32)


def h264_dequantize_luma_dc(levels, qp):
    """Dequantize luma DC *after* the inverse Hadamard (spec §8.5.10).

    dcY = (f * V00 << (qp//6)) >> 2         if qp >= 12
        = (f * V00 + 2^(1 - qp//6)) >> (2 - qp//6)   otherwise
    """
    f = jnp.asarray(levels, jnp.int32)
    if _is_static_qp(qp):
        v00 = int(V_TABLE[qp % 6][0, 0])
        if qp >= 12:
            return (f * v00) << (qp // 6 - 2)
        shift = 2 - qp // 6
        return (f * v00 + (1 << (shift - 1))) >> shift
    q = _vq(qp, f.ndim)
    v00 = jnp.asarray(_V_A)[q % 6]
    hi = (f * v00) << jnp.maximum(q // 6 - 2, 0)
    shift = jnp.maximum(2 - q // 6, 1)          # qp < 12 -> shift in {1, 2}
    lo = (f * v00 + jnp.left_shift(1, shift - 1)) >> shift
    return jnp.where(q >= 12, hi, lo)


def h264_quantize_chroma_dc(dc_hadamard, qp_c, intra: bool = True):
    """Quantize the 2x2 Hadamard chroma DC (JM convention: qbits + 1)."""
    w = jnp.asarray(dc_hadamard, jnp.int32)
    if _is_static_qp(qp_c):
        qbits = 15 + qp_c // 6
        mf00 = int(MF_TABLE[qp_c % 6][0, 0])
        f = (1 << qbits) // 3 if intra else (1 << qbits) // 6
        level = (jnp.abs(w) * mf00 + 2 * f) >> (qbits + 1)
        return (jnp.sign(w) * level).astype(jnp.int32)
    q = _vq(qp_c, w.ndim)
    qbits = 15 + q // 6
    mf00 = jnp.asarray(_MF_A)[q % 6]
    f = jnp.left_shift(1, qbits) // (3 if intra else 6)
    level = (jnp.abs(w) * mf00 + 2 * f) >> (qbits + 1)
    return (jnp.sign(w) * level).astype(jnp.int32)


def h264_dequantize_chroma_dc(levels, qp_c):
    """Dequantize chroma DC after inverse 2x2 Hadamard (spec §8.5.11).

    dcC = ((f * V00) << (qp_c//6)) >> 1
    """
    f = jnp.asarray(levels, jnp.int32)
    if _is_static_qp(qp_c):
        v00 = int(V_TABLE[qp_c % 6][0, 0])
        return ((f * v00) << (qp_c // 6)) >> 1
    q = _vq(qp_c, f.ndim)
    v00 = jnp.asarray(_V_A)[q % 6]
    return ((f * v00) << (q // 6)) >> 1
