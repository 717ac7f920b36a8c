"""The GOP-chunk super-step (:func:`build_p_chunk_step`, the served ring)
and the link probe (:func:`measure_link_rtt`, obs/budget's).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax


# ---------------------------------------------------------------------------
# Persistent compiled serving graph: the GOP-chunk SUPER-STEP
#
# The per-frame serving loop crosses Python once per frame,
# which caps pipelined throughput far below what the device sustains
# intra.  The super-step moves the whole P-run loop INTO XLA: one jitted
# call encodes a GOP-chunk of K frames via ``lax.scan``, chaining the
# reconstruction (and in-loop deblock) through the scan carry exactly as
# the per-frame path chains it through ``self._ref`` — so the emitted
# bitstream is byte-identical (tested GOP-deep), while the host pays ONE
# dispatch per chunk instead of K.
#
# Ring-buffer donation: the reference planes are ``donate_argnames``'d
# and the new reference is returned in the same position/shape/dtype, so
# XLA aliases the buffers — iteration N+1's ref ring IS iteration N's
# output ring, never a copy, and matching in/out layout means chained
# chunk calls never repartition (the pjit contract SNIPPETS.md [1]/[3]
# prescribes: out specs of call N == in specs of call N+1).  The frame
# ring (ys/cbs/crs) is deliberately NOT donated: no output shares its
# shape, so donation could never alias it and would only emit
# "unusable donation" warnings; XLA frees it after the scan regardless.
#
# ``prefix_len`` bakes the host's pull-guess bucket into the program so
# the chunk's bitstream prefix is an OUTPUT of the same dispatch — the
# steady-state submit path is exactly one Python crossing per chunk
# (guess changes are bucketed decaying-max, so a re-bucket costs one
# recompile, which the retrace tripwire test pins).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def build_p_chunk_step(qp: int, deblock: bool = True,
                       entropy: str = "cavlc", ingest: str = "yuv",
                       prefix_len: int = 0, spatial_shards: int = 1,
                       tune: str = "off", p_intra: bool = False,
                       damage_bucket: int = 0):
    """Build the jitted GOP-chunk super-step for one (qp, deblock,
    entropy, ingest, prefix_len, spatial_shards) configuration.

    ``spatial_shards > 1`` grows the program a SPATIAL axis: the same
    K-frame donated-ring scan, but each frame's MB rows sharded across
    that many chips inside ``shard_map`` — halo exchange and sharded
    deblock inside the scan body, per-shard entropy gathered per frame
    (``parallel.batch.h264_spatial_chunk_step`` is the implementation;
    this builder is the single serving entry).  Same 7-tuple contract
    with ``flats``/``prefix`` carrying an extra shard axis
    ``(K, nx, L)``; the ref ring is donated and returned under one
    fixed ``P("spatial", None)`` spec so chained chunks never
    repartition.  Spatial mode requires ``ingest="yuv"`` (planes are
    staged pre-converted; splitting an RGB frame's 4:2:0 subsample
    across a shard seam would change rounding at the boundary).

    The returned callable specializes per input SHAPE (chunk size and
    geometry are carried by the arrays), so one builder result serves
    every chunk length and every geometry bucket with one compile each:

    - ``entropy="cavlc"``:   ``step(ys, cbs, crs, ref_y, ref_cb, ref_cr,
      hv, hl) -> (flats, prefix, ref_y', ref_cb', ref_cr', mvs,
      levels)`` where ``ys`` is ``(K, H, W)`` uint8 (``(K, h, w, 3)``
      RGB under ``ingest="rgb"``, fusing the capture-ingest YUV
      conversion into the same program), ``hv``/``hl`` are the K frames'
      slice-header slots stacked on axis 0, ``flats`` is ``(K, L)`` and
      ``prefix`` its first ``prefix_len`` bytes per frame (0 = whole
      buffer; the host prefetches only the prefix).
    - ``entropy="cabac"``:   same signature minus ``hv``/``hl`` —
      emits the device-binarized (bin, ctxIdx, bypass) record streams
      (ops/cabac_binarize); the host replays only the arithmetic engine.

    ``mvs``/``levels`` stay lazy on device and cross the link only on a
    flat-cap overflow (host-entropy fallback of the same levels).

    ``damage_bucket > 0`` builds the DAMAGE-MASKED chunk scan
    (ops/damage_mask): each staged frame carries a ``(damage_bucket,)``
    damaged-row worklist plus that worklist's gathered slice-header
    slots, and the scan body runs ``damage_mask.row_core`` — the same
    row-compacted core the per-frame masked step jits, so the two
    paths' bytes cannot drift.  The bucket is static (one compile per
    ladder rung); ``flats`` becomes ``(K, L_b)`` with each frame's meta
    describing ``damage_bucket`` rows; the ref ring is still donated,
    the recon rows scattered in place.  Signature gains a trailing
    ``rows`` argument: ``step(ys, cbs, crs, ref_y, ref_cb, ref_cr,
    hv_r, hl_r, rows)`` with ``hv_r``/``hl_r`` shaped
    ``(K, damage_bucket, S)`` and ``rows`` ``(K, damage_bucket)``.
    Masked chunks require cavlc entropy, yuv ingest, single shard.
    """
    from . import cabac_binarize, cavlc_p_device, h264_deblock, h264_inter
    from .h264_device import nnz_blocks_raster

    if entropy not in ("cavlc", "cabac"):
        raise ValueError(f"unknown chunk entropy {entropy!r}")
    if ingest not in ("yuv", "rgb"):
        raise ValueError(f"unknown chunk ingest {ingest!r}")
    if damage_bucket > 0 and (entropy != "cavlc" or ingest != "yuv"
                              or spatial_shards > 1):
        raise ValueError("masked chunk requires cavlc entropy, yuv "
                         "ingest and a single spatial shard")
    if tune == "hq" and entropy == "cabac":
        # the binarize record stream has no qp plumbing; models/h264
        # keeps hq CABAC on the dense host path (ring ineligible)
        raise ValueError("tune=hq chunk requires cavlc entropy")
    if p_intra and (entropy != "cavlc" or deblock):
        raise ValueError("p_intra requires cavlc entropy, deblock off")
    if spatial_shards > 1:
        if ingest != "yuv":
            raise ValueError("spatial chunk step requires yuv ingest")
        from ..parallel import batch
        mesh = batch.make_spatial_mesh(spatial_shards)
        return batch.h264_spatial_chunk_step(
            mesh, qp=qp, deblock=deblock, entropy=entropy,
            prefix_len=prefix_len, tune=tune, p_intra=p_intra)

    def ingest_frame(frame, pad_h: int, pad_w: int):
        if ingest == "yuv":
            return frame            # (y, cb, cr) tuple, already padded
        # fused capture-ingest: byte-identical to models.h264._yuv_stage
        from . import color
        h, w = frame.shape[0], frame.shape[1]
        rgb_p = jnp.pad(frame, ((0, pad_h - h), (0, pad_w - w), (0, 0)),
                        mode="edge")
        y, cb, cr = color.rgb_to_yuv420(rgb_p, matrix="video")
        q = lambda p: jnp.clip(jnp.round(p), 0, 255).astype(jnp.uint8)
        return q(y), q(cb), q(cr)

    def one_frame(frame, ry, rcb, rcr, hv_f, hl_f, next_y=None):
        pad_h, pad_w = ry.shape
        y, cb, cr = ingest_frame(frame, pad_h, pad_w)
        if entropy == "cavlc":
            flat, ny, ncb, ncr, mv, nnz, lv = \
                cavlc_p_device.encode_p_cavlc_frame.__wrapped__(
                    y, cb, cr, ry, rcb, rcr, hv_f, hl_f, qp, tune,
                    next_y, p_intra)
        else:
            out = h264_inter.encode_p_frame.__wrapped__(
                y, cb, cr, ry, rcb, rcr, qp, tune, next_y)
            ny, ncb, ncr = (out["recon_y"], out["recon_cb"],
                            out["recon_cr"])
            mv = out["mv"]
            nnz = nnz_blocks_raster(out["luma"])
            flat = cabac_binarize.binarize_p(
                out["mv"], out["luma"], out["cb_dc"], out["cb_ac"],
                out["cr_dc"], out["cr_ac"])
            lv = {k: out[k] for k in ("luma", "cb_dc", "cb_ac",
                                      "cr_dc", "cr_ac")}
        if deblock:
            ny, ncb, ncr = h264_deblock.deblock_frame.__wrapped__(
                ny, ncb, ncr, qp, nnz_blk=nnz, mv=mv.astype(jnp.int32))
        return flat, ny, ncb, ncr, mv, lv

    def scan_chunk(frames_xs, ref_y, ref_cb, ref_cr, hv, hl, rows=None):
        """frames_xs: (rgbs,) under rgb ingest, (ys, cbs, crs) under
        yuv.  Returns the 7-tuple the serving ring dequeues."""
        def body(carry, xs):
            ry, rcb, rcr = carry
            next_y = None
            if tune == "hq":
                *xs, next_y = xs
            if damage_bucket > 0:
                # masked scan body: the per-frame masked step's core
                # verbatim (row_core pads refs, gathers the worklist's
                # bands, deblocks in-program, scatters recon in place)
                from . import damage_mask
                y, cbf, crf, hv_f, hl_f, rows_f = xs
                flat, ny, ncb, ncr, mv, nnz, lv = damage_mask.row_core(
                    y, cbf, crf, ry, rcb, rcr, rows_f, hv_f, hl_f, qp,
                    tune=tune, next_y=next_y, p_intra=p_intra,
                    deblock=deblock)
                return (ny, ncb, ncr), (flat, mv, lv)
            if entropy == "cavlc":
                *frame_parts, hv_f, hl_f = xs
            else:
                frame_parts, hv_f, hl_f = xs, None, None
            frame = (frame_parts[0] if ingest == "rgb"
                     else tuple(frame_parts))
            if next_y is not None and ingest == "rgb":
                # lookahead needs the NEXT frame's luma: ingest it (the
                # hq axis trades device cycles for bits by design)
                next_y = ingest_frame(next_y, *ry.shape)[0]
            flat, ny, ncb, ncr, mv, lv = one_frame(
                frame, ry, rcb, rcr, hv_f, hl_f, next_y)
            return (ny, ncb, ncr), (flat, mv, lv)

        xs = tuple(frames_xs) + ((hv, hl) if entropy == "cavlc" else ())
        if damage_bucket > 0:
            xs = xs + (rows,)
        if tune == "hq":
            # 1-frame lookahead over the staged ring: frame k pre-biases
            # its qp plane with frame k+1 (the last frame sees itself —
            # the full static bias, mirrored by models/h264._ring_flush)
            lead = frames_xs[0]
            xs = xs + (jnp.concatenate([lead[1:], lead[-1:]], axis=0),)
        (ry, rcb, rcr), (flats, mvs, lvs) = lax.scan(
            body, (ref_y, ref_cb, ref_cr), xs)
        prefix = flats if prefix_len <= 0 else flats[:, :prefix_len]
        return flats, prefix, ry, rcb, rcr, mvs, lvs

    from .h264_inter import RING_DONATE

    if ingest == "rgb":
        @functools.partial(jax.jit, donate_argnames=RING_DONATE)
        def chunk_step(rgbs, ref_y, ref_cb, ref_cr, hv=None, hl=None):
            return scan_chunk((rgbs,), ref_y, ref_cb, ref_cr, hv, hl)
    else:
        @functools.partial(jax.jit, donate_argnames=RING_DONATE)
        def chunk_step(ys, cbs, crs, ref_y, ref_cb, ref_cr,
                       hv=None, hl=None, rows=None):
            return scan_chunk((ys, cbs, crs), ref_y, ref_cb, ref_cr,
                              hv, hl, rows)
    return chunk_step


@jax.jit
def _probe_loop(x, steps):
    """Trivial device-resident loop for the link probe: the work is a few
    integer adds (sub-microsecond on any backend), so the wall-clock of a
    small-k call is dominated by dispatch + the 4-byte result pull — i.e.
    by the host<->device link, not by compute."""
    def body(i, acc):
        return acc + x[i % 8, i % 8].astype(jnp.uint32)

    return lax.fori_loop(0, steps, body, jnp.uint32(0))


def measure_link_rtt(reps: int = 7, k_hi: int = 257) -> dict:
    """Estimate the host<->device round-trip cost of one dispatch+pull.

    A differencing trick: the wall clock of a k-step call is
    ``t(k) = rtt + k * step`` — two trip counts give ``step``, and
    ``rtt = t_lo - k_lo * step`` is the fixed per-call cost (dispatch,
    transfer-out of the 4-byte checksum).
    This is the number the serving-budget ledger subtracts from the
    collect stage to separate link cost from compute (obs/budget).

    Returns {"rtt_ms", "step_us", "samples"}; rtt_ms is the median of
    ``reps`` k=1 calls minus the per-step cost.
    """
    x = jax.device_put(np.zeros((8, 8), np.uint8))
    np.asarray(_probe_loop(x, jnp.int32(1)))          # compile + warm
    lo = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(_probe_loop(x, jnp.int32(1)))
        lo.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    np.asarray(_probe_loop(x, jnp.int32(k_hi)))
    t_hi = time.perf_counter() - t0
    lo_sorted = sorted(lo)
    t_lo = lo_sorted[len(lo_sorted) // 2]             # median: RTT jitters
    step_s = max((t_hi - t_lo) / (k_hi - 1), 0.0)
    rtt_s = max(t_lo - step_s, 0.0)
    return {"rtt_ms": round(rtt_s * 1e3, 3),
            "step_us": round(step_s * 1e6, 3),
            "samples": [round(v * 1e3, 3) for v in lo_sorted]}
