"""In-graph content statistics: the device kernels of the content &
quality telemetry plane (obs/content, ISSUE 17).

Every served frame gets a small per-frame stats vector computed ON
DEVICE, dispatched inside the encoder's existing submit event so the
steady-state Python->device crossing count is exactly unchanged
(models/h264 counts ONE crossing per submit via ``_count_dispatch``
regardless of how many jitted calls ride that event — the deblock and
binarize stages already share a crossing the same way):

- luma **PSNR** of the closed-loop reconstruction vs the source (as an
  integer-exact per-MB SSE reduced in float32 — the float32 sum of
  <=2^24 per-MB int32 SSEs is far inside the 0.01 dB oracle tolerance);
- per-MB frame-diff **damage fraction**: the fraction of macroblocks
  whose summed abs diff vs the *previous ingest* exceeds a threshold,
  plus the full 0/1 MB damage grid (downsampled host-side for the
  ``/debug/content`` heatmap — the grid itself is tiny, <=8 KB at 4K);
- **mode mix** (skip / inter / intra MB counts — "skip" is the
  telemetry proxy ``zero MV & no coded residual``, which over-counts
  true P_Skip only when the MV predictor is nonzero);
- mean and p95 **|MV|** in quarter-pel units;
- ``ops/aq.mb_activity`` **percentiles** (p50/p95) — the AQ substrate
  ROADMAP item 3's damage-driven encode will gate on.

The kernels read encode inputs/outputs and never feed anything back
into the encode programs, so bitstreams are byte-identical with the
plane on or off (tested GOP-deep across the per-frame, super-step
chunk, and spatial-shard paths).  Donation discipline: reconstruction
planes alias the donated reference ring, so callers must dispatch
these stats at SUBMIT time, while the recon handle is still live —
the outputs are tiny fresh buffers that survive any later donation.

Every device kernel has a numpy twin (``*_np``) used as the test
oracle and as the VP8 host path's implementation.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .aq import _mb_sum, mb_activity

__all__ = ["VEC_LEN", "frame_stats", "chunk_stats", "frame_stats_np",
           "mb_activity_np", "psnr_from_sse", "vec_to_stats",
           "downsample_grid"]

# stats-vector slot layout (float32; -1.0 marks "not computed")
VEC_LEN = 10
IDX_SSE = 0        # luma SSE vs recon (-1 = no recon in reach)
IDX_DAMAGE = 1     # damaged-MB count (-1 = no previous ingest)
IDX_SKIP = 2       # skip-proxy MB count (-1 = no mode info)
IDX_INTER = 3      # coded inter MB count
IDX_INTRA = 4      # intra MB count
IDX_MV_MEAN = 5    # mean |MV|, quarter-pel (-1 = no MV field)
IDX_MV_P95 = 6     # p95 |MV|, quarter-pel
IDX_ACT_P50 = 7    # ops/aq.mb_activity p50
IDX_ACT_P95 = 8    # ops/aq.mb_activity p95
IDX_MBS = 9        # macroblock count (denominator, sanity echo)


# ---------------------------------------------------------------------------
# device pieces (shared by the per-frame and chunk kernels)
# ---------------------------------------------------------------------------

def _damage_grid(y, prev_y, thr_sad: int):
    """(H, W) luma pair -> (R, C) uint8 damage flags: per-MB summed abs
    diff > ``thr_sad`` (the knob is a mean-per-pixel threshold scaled by
    256 host-side, so the device compare stays integer-exact)."""
    d = jnp.abs(jnp.asarray(y, jnp.int32) - jnp.asarray(prev_y, jnp.int32))
    sad = _mb_sum(d)                                   # (R, C) int32
    return (sad > thr_sad).astype(jnp.uint8)


def _luma_sse(y, recon_y):
    """Integer-exact per-MB SSE (max 256*255^2 < 2^31 per MB), summed in
    float32 — relative error ~1e-7, versus the 0.23% MSE slack a 0.01 dB
    PSNR tolerance allows."""
    d = jnp.asarray(y, jnp.int32) - jnp.asarray(recon_y, jnp.int32)
    mb_sse = _mb_sum(d * d)                            # (R, C) int32
    return jnp.sum(mb_sse.astype(jnp.float32))


def _activity_pcts(y):
    act = mb_activity(y).astype(jnp.float32).reshape(-1)
    return jnp.percentile(act, jnp.asarray([50.0, 95.0], jnp.float32))


def _mv_stats(mv):
    """(R, C, 2) quarter-pel MV field -> (mean |MV|, p95 |MV|)."""
    m = jnp.asarray(mv, jnp.float32)
    mag = jnp.sqrt(jnp.sum(m * m, axis=-1)).reshape(-1)
    return jnp.mean(mag), jnp.percentile(mag, 95.0)


def _mode_counts(mv, resid: Sequence, mb_intra):
    """Per-MB mode mix from the MV field + residual tensors: ``coded``
    is any nonzero level in any residual plane of the MB; skip is the
    zero-MV & uncoded & non-intra proxy."""
    r, c = mv.shape[:2]
    coded = jnp.zeros((r, c), bool)
    for t in resid:
        coded = coded | jnp.any(
            jnp.asarray(t).reshape(r, c, -1) != 0, axis=-1)
    zero_mv = jnp.all(jnp.asarray(mv) == 0, axis=-1)
    if mb_intra is not None:
        intra = jnp.asarray(mb_intra, bool)
    else:
        intra = jnp.zeros((r, c), bool)
    n_intra = jnp.sum(intra)
    n_skip = jnp.sum((~coded) & zero_mv & (~intra))
    n_inter = r * c - n_intra - n_skip
    return n_skip, n_inter, n_intra


def _frame_vec(y, prev_y, recon_y, mv, resid, mb_intra, thr_sad: int):
    """One frame's stats vector + damage grid (traced pieces; optional
    inputs arrive as None and pin the matching slots at -1)."""
    h, w = y.shape
    r, c = h // 16, w // 16
    neg = jnp.float32(-1.0)
    if prev_y is not None:
        grid = _damage_grid(y, prev_y, thr_sad)
        n_damage = jnp.sum(grid, dtype=jnp.int32).astype(jnp.float32)
    else:
        grid = jnp.zeros((r, c), jnp.uint8)
        n_damage = neg
    sse = _luma_sse(y, recon_y) if recon_y is not None else neg
    if mv is not None:
        mv_mean, mv_p95 = _mv_stats(mv)
    else:
        mv_mean = mv_p95 = neg
    if mv is not None and resid:
        n_skip, n_inter, n_intra = _mode_counts(mv, resid, mb_intra)
        n_skip = n_skip.astype(jnp.float32)
        n_inter = jnp.asarray(n_inter, jnp.float32)
        n_intra = n_intra.astype(jnp.float32)
    else:
        n_skip = n_inter = n_intra = neg
    a50, a95 = _activity_pcts(y)
    vec = jnp.stack([sse, n_damage, n_skip, n_inter, n_intra,
                     mv_mean, mv_p95, a50, a95,
                     jnp.float32(r * c)])
    return vec, grid


@functools.partial(jax.jit, static_argnames=("thr_sad",))
@jax.named_scope("dngd.frame_stats")
# NOT donated on purpose: prev_y is the PREVIOUS frame's ingest luma,
# which the encoder keeps alive across frames (next frame's stats diff
# against it) — donating it would invalidate the caller's held buffer.
# dngd: ignore[jax-donate-missing]
def frame_stats(y, prev_y, recon_y, mv, resid, mb_intra, thr_sad: int):
    """Per-frame device stats: ``(vec, grid)`` with ``vec`` float32
    ``(VEC_LEN,)`` and ``grid`` uint8 ``(R, C)``.  ``prev_y`` /
    ``recon_y`` / ``mv`` / ``mb_intra`` may be None; ``resid`` is a
    (possibly empty) tuple of residual level tensors reshaped per MB.
    Specializes per optional-arg presence via the pytree structure."""
    return _frame_vec(y, prev_y, recon_y, mv, resid, mb_intra, thr_sad)


@functools.partial(jax.jit, static_argnames=("thr_sad",))
@jax.named_scope("dngd.frame_stats")
# NOT donated on purpose: prev_y (the previous chunk's last ingest) and
# the staged ys stack stay owned by the encoder's ring across chunks.
# dngd: ignore[jax-donate-missing]
def chunk_stats(ys, prev_y, recon_last_y, mvs, resid, thr_sad: int):
    """Super-step chunk stats: ``ys`` is the staged ``(K, H, W)`` luma
    stack; each slot diffs against its predecessor (slot 0 against
    ``prev_y``, the previous chunk's last ingest).  The reference ring
    keeps only the LAST slot's reconstruction, so SSE lands in slot
    K-1 only (-1 elsewhere — the plane samples PSNR at chunk cadence).
    ``mvs`` is ``(K, R, C, 2)`` (or None), ``resid`` a tuple of
    ``(K, ...)``-stacked level tensors.  Returns ``(vecs, grids)`` of
    shapes ``(K, VEC_LEN)`` / ``(K, R, C)``."""
    k = ys.shape[0]
    if prev_y is not None:
        prevs = jnp.concatenate([jnp.asarray(prev_y, ys.dtype)[None],
                                 ys[:-1]], axis=0)
        grids = jax.vmap(lambda a, b: _damage_grid(a, b, thr_sad))(
            ys, prevs)
        n_damage = jnp.sum(grids, axis=(1, 2), dtype=jnp.int32
                           ).astype(jnp.float32)
    else:
        r, c = ys.shape[1] // 16, ys.shape[2] // 16
        grids = jnp.zeros((k, r, c), jnp.uint8)
        n_damage = jnp.full((k,), -1.0, jnp.float32)
    r, c = ys.shape[1] // 16, ys.shape[2] // 16
    neg = jnp.full((k,), -1.0, jnp.float32)
    sse = neg
    if recon_last_y is not None:
        sse = sse.at[k - 1].set(_luma_sse(ys[k - 1], recon_last_y))
    if mvs is not None:
        mv_mean, mv_p95 = jax.vmap(_mv_stats)(mvs)
    else:
        mv_mean = mv_p95 = neg
    if mvs is not None and resid:
        n_skip, n_inter, n_intra = jax.vmap(
            lambda m, *ts: _mode_counts(m, ts, None))(mvs, *resid)
        n_skip = n_skip.astype(jnp.float32)
        n_inter = jnp.asarray(n_inter, jnp.float32)
        n_intra = n_intra.astype(jnp.float32)
    else:
        n_skip = n_inter = n_intra = neg
    a = jax.vmap(_activity_pcts)(ys)                   # (K, 2)
    vecs = jnp.stack([sse, n_damage, n_skip, n_inter, n_intra,
                      mv_mean, mv_p95, a[:, 0], a[:, 1],
                      jnp.full((k,), float(r * c), jnp.float32)],
                     axis=1)
    return vecs, grids


# ---------------------------------------------------------------------------
# numpy twins: test oracles + the VP8 host path
# ---------------------------------------------------------------------------

def mb_activity_np(y: np.ndarray) -> np.ndarray:
    """Numpy twin of :func:`ops.aq.mb_activity` (int32-exact)."""
    yi = np.asarray(y, np.int64)
    h, w = yi.shape
    t = yi.reshape(h // 16, 16, w // 16, 16)
    s = t.sum(axis=(1, 3))
    s2 = (t * t).sum(axis=(1, 3))
    return np.maximum(256 * s2 - s * s, 0).astype(np.int64)


def frame_stats_np(y, prev_y=None, recon_y=None, mv=None, resid=(),
                   mb_intra=None, thr_sad: int = 512
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Host oracle of :func:`frame_stats` — same vector layout, same
    -1 sentinels, float64 accumulation (the tolerance the device's
    float32 SSE sum is tested against)."""
    y = np.asarray(y)
    h, w = y.shape
    r, c = h // 16, w // 16
    vec = np.full(VEC_LEN, -1.0, np.float64)
    vec[IDX_MBS] = r * c
    if prev_y is not None:
        d = np.abs(y.astype(np.int64) - np.asarray(prev_y, np.int64))
        sad = d.reshape(r, 16, c, 16).sum(axis=(1, 3))
        grid = (sad > thr_sad).astype(np.uint8)
        vec[IDX_DAMAGE] = float(grid.sum())
    else:
        grid = np.zeros((r, c), np.uint8)
    if recon_y is not None:
        d = y.astype(np.int64) - np.asarray(recon_y, np.int64)
        vec[IDX_SSE] = float((d * d).sum())
    if mv is not None:
        m = np.asarray(mv, np.float64)
        mag = np.sqrt((m * m).sum(axis=-1)).reshape(-1)
        vec[IDX_MV_MEAN] = float(mag.mean())
        vec[IDX_MV_P95] = float(np.percentile(mag, 95.0))
    if mv is not None and len(resid):
        coded = np.zeros((r, c), bool)
        for t in resid:
            coded |= (np.asarray(t).reshape(r, c, -1) != 0).any(axis=-1)
        zero_mv = (np.asarray(mv) == 0).all(axis=-1)
        intra = (np.asarray(mb_intra, bool) if mb_intra is not None
                 else np.zeros((r, c), bool))
        vec[IDX_INTRA] = float(intra.sum())
        vec[IDX_SKIP] = float(((~coded) & zero_mv & (~intra)).sum())
        vec[IDX_INTER] = r * c - vec[IDX_INTRA] - vec[IDX_SKIP]
    act = mb_activity_np(y).astype(np.float64).reshape(-1)
    vec[IDX_ACT_P50] = float(np.percentile(act, 50.0))
    vec[IDX_ACT_P95] = float(np.percentile(act, 95.0))
    return vec, grid


# ---------------------------------------------------------------------------
# host-side decoding of the stats vector
# ---------------------------------------------------------------------------

def psnr_from_sse(sse: float, npix: int) -> Optional[float]:
    """Luma PSNR in dB from a summed SSE; None when the sentinel says
    no recon was in reach, 99.0 on an exact match (ops/aq convention)."""
    if sse is None or sse < 0:
        return None
    if sse <= 0:
        return 99.0
    return float(10.0 * np.log10(255.0 * 255.0 * npix / sse))


def vec_to_stats(vec: np.ndarray, grid: np.ndarray, npix: int) -> dict:
    """Decode one fetched stats vector + grid into the plain dict the
    content plane records (None for the -1 'not computed' slots)."""
    vec = np.asarray(vec, np.float64)
    mbs = max(int(vec[IDX_MBS]), 1)
    out = {
        "psnr_db": psnr_from_sse(float(vec[IDX_SSE]), npix),
        "damage_fraction": (float(vec[IDX_DAMAGE]) / mbs
                            if vec[IDX_DAMAGE] >= 0 else None),
        "damage_grid": np.asarray(grid, np.uint8),
        "mv_mean_qpel": (float(vec[IDX_MV_MEAN])
                         if vec[IDX_MV_MEAN] >= 0 else None),
        "mv_p95_qpel": (float(vec[IDX_MV_P95])
                        if vec[IDX_MV_P95] >= 0 else None),
        "act_p50": float(vec[IDX_ACT_P50]),
        "act_p95": float(vec[IDX_ACT_P95]),
        "mbs": mbs,
    }
    if vec[IDX_SKIP] >= 0:
        out["mode"] = {"skip": float(vec[IDX_SKIP]) / mbs,
                       "inter": float(vec[IDX_INTER]) / mbs,
                       "intra": float(vec[IDX_INTRA]) / mbs}
    else:
        out["mode"] = None
    return out


def downsample_grid(grid: np.ndarray, max_w: int = 32,
                    max_h: int = 18) -> np.ndarray:
    """Block-mean a (R, C) 0/1 MB damage grid down to at most
    ``max_h x max_w`` float cells for the /debug/content heatmap."""
    g = np.asarray(grid, np.float64)
    r, c = g.shape
    br = -(-r // max_h)
    bc = -(-c // max_w)
    if br > 1 or bc > 1:
        pr = -(-r // br) * br - r
        pc = -(-c // bc) * bc - c
        g = np.pad(g, ((0, pr), (0, pc)), constant_values=np.nan)
        g = np.nanmean(
            g.reshape(g.shape[0] // br, br, g.shape[1] // bc, bc),
            axis=(1, 3))
    return g
