"""Perceptual-efficiency tuning kernels (ENCODER_TUNE=hq, ROADMAP item 4).

Three device-side pieces that trade device cycles for bits — the NVENC
tuning-ladder analog (PAPERS.md: "Evolution of NVENC Efficiency"):

1. **Adaptive per-MB quantization** (:func:`aq_offsets`): a per-MB QP
   delta plane from luma activity (variance), two per-macroblock sums
   over the plane (:func:`_mb_sum`).  Low-activity (flat) macroblocks
   quantize finer — they are cheap in bits and visually/numerically
   dominant; high-activity blocks absorb coarser quantization.  The map
   is a PURE PER-MB function (log-activity against a fixed reference
   energy, no frame-level normalization), which is what makes it safe in
   every execution shape: the spatially-sharded mesh, the donated-ring
   chunk scan, and the per-frame path all compute identical planes.
   The frame's mean coded QP therefore moves with content; the
   RateController normalizes its +6-qp-halves-bits model by the *mean
   coded* QP, not the nominal ladder value (models/h264).

2. **Lambda tables** (:func:`lam_mode` / :func:`lam_mv`): the standard
   H.264 Lagrangian lambda(QP) = 0.85 * 2^((QP-12)/3) for SSD-domain
   mode decisions and its square root for SAD-domain motion decisions.
   Mode/MV choices then minimize D + lambda * R instead of the fixed
   bits-only / fixed-SAD-margin heuristics.

3. **1-frame lookahead bias** (:func:`lookahead_bias`): per-MB SAD
   between the current and NEXT frame (the chunk ring's already-staged
   frames — zero extra transfers).  Static content earns a negative
   delta (its quality propagates through the P chain), fast-changing
   content a positive one (those bits are washed away next frame).

Everything here is elementwise/reduction VPU work that XLA fuses into
the surrounding encode kernels; tune=off paths never call into this
module, which is what keeps them byte-identical to the pre-tune output.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["aq_offsets", "lookahead_bias", "lam_mode", "lam_mv",
           "qp_plane", "qp_chain", "qp_chain_np", "mse_planes",
           "AQ_STRENGTH", "AQ_MAX_DELTA", "AQ_MAX_UP", "LOOKAHEAD_BIAS"]

# Strength in ~x264 aq-strength units, the delta clamps, and the
# lookahead reward.
# The up/down clamps are ASYMMETRIC: lifting flat blocks
# (negative delta) buys PSNR cheaply — they cost few bits — while
# coarsening busy blocks trades a lot of measured distortion for modest
# savings, so the up side caps at +1 (the perceptual-masking headroom
# is real but the BD-rate harness scores PSNR, and a +1 cap keeps hq
# strictly non-losing there while still shaving busy-block bits).
AQ_STRENGTH = 1.0
AQ_MAX_DELTA = 4
AQ_MAX_UP = 1
LOOKAHEAD_BIAS = 2

# Reference log2 activity: a 16x16 block whose summed squared deviation
# (256 * per-pixel variance) is ~2^_AQ_REF_LOG sits at delta 0.  12.0
# corresponds to per-pixel variance 16 — typical desktop-content
# mid-energy (empirically centers the map on the bench's three content
# classes).
_AQ_REF_LOG = 12.0


def _mb_sum(plane):
    """(H, W) -> (R, C) per-16x16-MB sum, rows first and then columns.

    The rows are summed with the picture's width on the lanes, and only
    the sixteenth-sized (R, W) result is split by columns.  Summed in one
    step over ``reshape(R, 16, C, 16)`` a frame-sized array has a minor
    dimension of 16, which the TPU tiles (8, 128): eight times its size,
    and from 2560x1600 up too large for the fast memory (1.35 ms a frame
    of ``frame_stats`` at that size, 2.6 at 4K: PERF.md Findings, PR 44).
    Integer sums in either order: the same bits."""
    h, w = plane.shape
    rows = plane.reshape(h // 16, 16, w).sum(1)
    return rows.reshape(h // 16, w // 16, 16).sum(2)


def mb_activity(y):
    """Per-MB luma activity: sum of squared deviation from the MB mean
    (256 * variance), int32-exact."""
    yi = jnp.asarray(y, jnp.int32)
    s = _mb_sum(yi)                                   # (R, C)
    s2 = _mb_sum(yi * yi)
    # 256 * var = sum(x^2) - sum(x)^2 / 256; keep integer via * 256
    return jnp.maximum(256 * s2 - s * s, 0)           # (R, C) ~2^24 max


def aq_offsets(y, strength: float = None, max_delta: int = None):
    """Per-MB QP delta plane from luma activity.

    delta = round(strength * (log2(act + 1) - REF) / 2) clipped to
    [-max_delta, +AQ_MAX_UP], where act = mb_activity/256 is the MB's
    summed squared deviation (256x the per-pixel variance).  The /2
    maps one doubling of activity to ~strength/2 qp steps — the x264
    aq-mode-1 slope; the asymmetric clip is PSNR-guarding (see the knob
    comment above).  Pure per-MB math: shard/chunk/per-frame agree."""
    s = AQ_STRENGTH if strength is None else float(strength)
    md = AQ_MAX_DELTA if max_delta is None else int(max_delta)
    act = mb_activity(y).astype(jnp.float32) / 256.0
    d = s * 0.5 * (jnp.log2(act + 1.0) - _AQ_REF_LOG)
    return jnp.clip(jnp.round(d), -md, min(AQ_MAX_UP, md)).astype(jnp.int32)


def lookahead_bias(y, next_y, bias: int = None):
    """Per-MB QP bias from the NEXT frame: -bias where the block barely
    changes (quality propagates through the P chain), +1 where it
    changes heavily (bits are washed away next frame), 0 between.
    Thresholds are per-pixel mean-abs-diff 1.0 / 6.0."""
    b = LOOKAHEAD_BIAS if bias is None else int(bias)
    d = jnp.abs(jnp.asarray(y, jnp.int32) - jnp.asarray(next_y, jnp.int32))
    sad = _mb_sum(d)                                  # (R, C), /256 = mean
    return jnp.where(sad <= 256, -b,
                     jnp.where(sad >= 6 * 256, 1, 0)).astype(jnp.int32)


def qp_plane(y, qp: int, next_y=None, strength: float = None,
             max_delta: int = None):
    """The hq paths' per-MB ABSOLUTE qp map: ladder qp + activity delta
    (+ lookahead bias when the next frame is staged), clipped to the
    coded range.  qp stays >= 1 so the se(v) slot widths stay tiny."""
    d = aq_offsets(y, strength, max_delta)
    if next_y is not None:
        d = d + lookahead_bias(y, next_y)
    return jnp.clip(qp + d, 1, 51).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Lagrangian lambdas (H.264 HM/JM convention)
# ---------------------------------------------------------------------------

def lam_mode(qp):
    """SSD-domain mode-decision lambda: 0.85 * 2^((qp-12)/3).  Accepts a
    static int (returns a Python float) or a per-MB array."""
    if isinstance(qp, (int, np.integer)):
        return 0.85 * 2.0 ** ((int(qp) - 12) / 3.0)
    q = jnp.asarray(qp, jnp.float32)
    return 0.85 * jnp.exp2((q - 12.0) / 3.0)


def lam_mv(qp):
    """SAD-domain motion lambda: sqrt(lam_mode)."""
    if isinstance(qp, (int, np.integer)):
        return float(np.sqrt(lam_mode(qp)))
    return jnp.sqrt(lam_mode(qp))


# ---------------------------------------------------------------------------
# mb_qp_delta chain (spec 7.4.5: QPY carries from the previous MB in
# decoding order; slice-per-row resets each row to the slice QP)
# ---------------------------------------------------------------------------

def qp_chain(qp_map, codes_delta, slice_qp: int):
    """Per-row effective-QP chain and the per-MB mb_qp_delta values.

    qp_map: (R, C) desired per-MB qp; codes_delta: (R, C) bool — the MBs
    whose syntax carries mb_qp_delta (I16 always; otherwise cbp != 0).
    Returns (eff_qp, delta): an MB that does not code the syntax keeps
    the previous MB's effective qp (delta is meaningless there and its
    slot is gated off by the caller).  eff_qp is what the deblocking
    filter would see; MBs without coefficients never dequantize, so
    quantizing everything at qp_map stays conformant.
    """
    qp_map = jnp.asarray(qp_map, jnp.int32)
    codes = jnp.asarray(codes_delta, bool)
    nr, nc = qp_map.shape
    idx = jnp.arange(nc, dtype=jnp.int32)[None, :]
    import jax
    j = jax.lax.cummax(jnp.where(codes, idx, -1), axis=1)  # last coded <= c
    eff = jnp.where(j >= 0,
                    jnp.take_along_axis(qp_map, jnp.clip(j, 0), axis=1),
                    slice_qp)
    prev = jnp.concatenate(
        [jnp.full((nr, 1), slice_qp, jnp.int32), eff[:, :-1]], axis=1)
    return eff, (qp_map - prev)


def qp_chain_np(qp_map: np.ndarray, codes_delta: np.ndarray,
                slice_qp: int):
    """Numpy twin of :func:`qp_chain` for the host entropy coders."""
    qp_map = np.asarray(qp_map, np.int32)
    codes = np.asarray(codes_delta, bool)
    nr, nc = qp_map.shape
    idx = np.arange(nc, dtype=np.int32)[None, :]
    j = np.maximum.accumulate(np.where(codes, idx, -1), axis=1)
    eff = np.where(j >= 0,
                   np.take_along_axis(qp_map, np.clip(j, 0, None), axis=1),
                   slice_qp).astype(np.int32)
    prev = np.concatenate(
        [np.full((nr, 1), slice_qp, np.int32), eff[:, :-1]], axis=1)
    return eff, (qp_map - prev).astype(np.int32)


# ---------------------------------------------------------------------------
# Device-side distortion reductions (the BD-rate bench's PSNR input)
# ---------------------------------------------------------------------------

def _mse_reduce(x, y):
    d = x.astype(jnp.int32) - y.astype(jnp.int32)
    return jnp.sum((d * d).astype(jnp.int64))


_mse_jit = None      # jitted lazily so importing aq never inits a backend


def mse_planes(a, b):
    """Mean squared error between two planes as ONE device reduction
    (float64-free: int64 SSE over uint8 planes is exact)."""
    global _mse_jit
    if _mse_jit is None:
        import jax
        _mse_jit = jax.jit(_mse_reduce)
    sse = float(np.asarray(_mse_jit(jnp.asarray(a), jnp.asarray(b))))
    n = int(np.prod(np.asarray(a).shape))
    return sse / max(n, 1)


def psnr_planes(a, b) -> float:
    m = mse_planes(a, b)
    if m <= 0:
        return 99.0
    return float(10.0 * np.log10(255.0 * 255.0 / m))
