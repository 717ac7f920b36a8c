"""Batched multi-session encode over a device mesh.

Axes:
- ``session`` — data parallelism over concurrent desktop sessions (the
  BASELINE config-5 ladder rung: 8x 1080p60 on a v5e-8, one session per
  chip).
- ``spatial`` — intra-frame parallelism over macroblock rows, the moral
  equivalent of sequence/context parallelism (SURVEY.md §5): a 4K frame's
  MCU grid is split across chips; per-shard symbol histograms are psum'd
  over the spatial axis so every shard packs with identical Huffman tables,
  then per-shard packed bitstreams are all-gathered and bit-concatenated on
  the host.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

try:
    from jax import shard_map
except ImportError:
    # pre-0.5 jax ships shard_map under experimental with check_rep
    # instead of check_vma; adapt so this module imports (and the
    # multi-chip path runs) on both
    from jax.experimental.shard_map import shard_map as _shard_map_legacy

    def shard_map(f, mesh=None, in_specs=None, out_specs=None,
                  check_vma=None, **kw):
        return _shard_map_legacy(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs,
                                 check_rep=bool(check_vma), **kw)

from ..obs import metrics as obsm
from ..obs.trace import next_frame_id, tracer
from ..ops import jpeg_device, quant

# Per-step dispatch histogram: how long the host spends handing one
# batched tick to the device (first call includes the jit compile, which
# lands in the +Inf bucket and is visible as such).
_M_DISPATCH = obsm.histogram(
    "dngd_batch_step_dispatch_ms",
    "Host-side dispatch time of one batched device step", ("step",))

# Batched-path spans land in their own trace track ('batch') so the
# multi-session dispatch renders alongside the per-frame pipeline at
# /debug/trace, and the serving-budget ledger can account them when a
# batch path is what serves (obs/budget subscribes by tracer name).
_TRACER = tracer("batch")


# -- degraded-geometry buckets (resilience/degrade) ----------------------
# The degradation ladder's resolution downshift must not explode the
# compiled-step population: batched serving groups sessions by PADDED
# geometry (one XLA executable per bucket, see BucketedStreamManager),
# so degraded geometries are drawn from a fixed scale ladder and snapped
# to the same MB (16 px) grid — every session degraded to the same level
# re-buckets into ONE shared bucket instead of N bespoke geometries.

DEGRADE_SCALES: Tuple[float, ...] = (1.0, 0.75, 0.5)


def geometry_bucket(width: int, height: int) -> Tuple[int, int]:
    """The (pad_h, pad_w) bucket key a raw geometry encodes under —
    the same MB padding the batch managers group sessions by."""
    return (-(-height // 16) * 16, -(-width // 16) * 16)


def degraded_geometry(width: int, height: int, level: int,
                      min_dim: int = 64) -> Tuple[int, int]:
    """The (w, h) for degradation ``level`` (0 = native) of a native
    geometry: scaled by :data:`DEGRADE_SCALES`, floored to the MB grid
    (so the result IS its own padded bucket — no edge padding waste on
    a degraded session), and clamped to ``min_dim``."""
    scale = DEGRADE_SCALES[max(0, min(level, len(DEGRADE_SCALES) - 1))]
    if scale >= 1.0:
        # level 0 IS the native geometry: restoring from the ladder must
        # return exactly where the session started, not its MB floor
        return width, height
    w = max(min_dim, int(width * scale) // 16 * 16)
    h = max(min_dim, int(height * scale) // 16 * 16)
    return w, h


# -- elastic failover planning (resilience/continuity leg 2) -------------
# A mesh chip dying mid-GOP must not abort the batch: the survivors
# re-bucket onto an (N-1)-device mesh and displaced sessions restart
# from their host-side GOP checkpoint behind a recovery IDR.  The
# planning is pure arithmetic (unit-testable without devices); the
# executable rebuild — which also rewires the halo-exchange ppermute
# neighbor pairs, since they are derived from the new spatial extent —
# happens in web/multisession.BatchStreamManager._rebuild_mesh.

def replan_mesh(n_sessions: int, n_devices: int, pad_h: int,
                want_nx: int = 1) -> Tuple[int, int]:
    """The N->N-1 re-bucketing rule: the largest (ns, nx) shape that
    fits ``n_devices`` surviving chips, with ``ns`` dividing the session
    batch (shard_map's requirement) and the MB rows splitting over
    ``nx`` (the spatial-shard requirement).  Prefers keeping the spatial
    extent the caller had (``want_nx``), shrinking it only when the row
    constraint or the device count forces it."""
    if n_devices < 1:
        raise ValueError("no surviving devices to replan onto")
    best = (1, 1)
    for nx in range(min(max(want_nx, 1), n_devices), 0, -1):
        if pad_h % (16 * nx):
            continue
        ns = n_devices // nx
        while ns > 1 and n_sessions % ns:
            ns -= 1
        if ns * nx > best[0] * best[1]:
            best = (ns, nx)
    return best


def elastic_degrade_level(n_sessions: int, n_chips: int) -> int:
    """Recommended degradation-ladder level after chip loss: each rung
    of :data:`DEGRADE_SCALES` claws back roughly the per-chip budget one
    lost chip cost.  0 while chips >= sessions (one-session-per-chip,
    the BASELINE config-5 shape, still holds); one level per halving of
    the chip:session ratio after that, capped at the ladder depth."""
    if n_chips >= n_sessions or n_chips < 1:
        return 0
    level = 0
    while n_chips * (2 ** level) < n_sessions \
            and level < len(DEGRADE_SCALES) - 1:
        level += 1
    return level


def _timed_step(fn, kind: str):
    """Wrap a jitted step so every dispatch feeds the histogram and the
    'batch' trace track (child resolved once; per-call cost is two
    perf_counter reads, one integer bucket add, one deque append)."""
    child = _M_DISPATCH.labels(kind)
    stage = f"batch-dispatch-{kind}"           # interned once, not per call

    def run(*args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dur = time.perf_counter() - t0
        child.observe(dur * 1e3)
        _TRACER.record_span(stage, t0, dur, next_frame_id())
        return out

    run.__wrapped__ = fn       # the jitted step itself (.lower, the name)
    return run


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              devices=None) -> Mesh:
    """Build a ("session", "spatial") mesh from a shape tuple.

    shape (ns, nx); defaults to all devices on the session axis.
    """
    devices = jax.devices() if devices is None else devices
    n = len(devices)
    if shape is None:
        shape = (n, 1)
    elif len(shape) == 1:
        shape = (shape[0], 1)
    ns, nx = shape
    assert ns * nx == n, f"mesh {shape} != {n} devices"
    dev_array = np.asarray(devices).reshape(ns, nx)
    return Mesh(dev_array, ("session", "spatial"))


def _session_transform(rgb, luma_q, chroma_q, pad_h, pad_w):
    """vmapped single-frame transform: (S, H, W, 3) -> blocked coeffs."""
    from ..models.mjpeg import _transform_stage
    fn = functools.partial(_transform_stage.__wrapped__,  # un-jitted body
                           pad_h=pad_h, pad_w=pad_w)
    return jax.vmap(lambda f: fn(f, luma_q, chroma_q))(rgb)


def batch_encode_step(mesh: Mesh, frame_h: int, frame_w: int,
                      quality: int = 85):
    """Build the jitted multi-session batch-encode step for this mesh.

    Returns step(frames, tables...) -> (packed_shards, total_bits, hists):
      frames: (S, H, W, 3) uint8, S sharded over "session", H over "spatial".
      packed_shards: (S, nx, bytes_per_shard); total_bits: (S, nx).
    Each spatial shard encodes with its DC predictors reset — exactly JPEG
    restart-marker semantics — so :func:`assemble_session_jpeg` joins shards
    with RSTn markers instead of bit-level stitching.
    """
    ns, nx = mesh.devices.shape
    assert frame_h % (16 * nx) == 0, "frame height must split into MCU rows"
    assert frame_w % 16 == 0, "frame width must be a multiple of 16"
    luma_q, chroma_q = quant.jpeg_quality_tables(quality)
    lq = jnp.asarray(luma_q, jnp.float32)
    cq = jnp.asarray(chroma_q, jnp.float32)

    def shard_fn(frames, *tables):
        # frames: (S/ns, H/nx, W, 3) local shard
        y_zz, cb, cr = _session_transform(frames, lq, cq,
                                          frames.shape[1], frames.shape[2])
        s_local = y_zz.shape[0]
        y_flat = y_zz.reshape(s_local, -1, 64)
        cb = cb.reshape(s_local, -1, 64)
        cr = cr.reshape(s_local, -1, 64)

        # Shared Huffman statistics across spatial shards (ICI collective):
        # histograms must agree so every shard packs with the same codes.
        def hists(yf, b, r):
            return jpeg_device.jpeg_analyze.__wrapped__(yf, b, r)
        h = jax.vmap(hists)(y_flat, cb, cr)
        h = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, axis_name="spatial"), h)

        def pack_one(yf, b, r):
            return jpeg_device.jpeg_pack.__wrapped__(yf, b, r, *tables)
        packed, total = jax.vmap(pack_one)(y_flat, cb, cr)
        # Expose every shard's bitstream to the session leader; transpose the
        # gathered axis behind the session axis -> (s_local, nx, nbytes).
        packed_all = jnp.swapaxes(
            jax.lax.all_gather(packed, axis_name="spatial"), 0, 1)
        total_all = jnp.swapaxes(
            jax.lax.all_gather(total, axis_name="spatial"), 0, 1)
        return packed_all, total_all, h

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("session", "spatial", None, None),) + (P(None),) * 8,
        # gathered/psum'd outputs are replicated across "spatial"
        out_specs=(P("session", None, None), P("session", None),
                   jax.tree_util.tree_map(lambda _: P("session"), (0, 0, 0, 0))),
        # check_vma=False: VMA checking rejects the replicated-out
        # psum/all_gather results these specs declare (jax 0.9 behavior);
        # re-enable when upstream accepts collective-produced replication
        check_vma=False,
    )
    return _timed_step(jax.jit(fn), "mjpeg")


def assemble_session_jpeg(packed_shards: np.ndarray, totals: np.ndarray,
                          tables, width: int, height: int,
                          quality: int = 85) -> bytes:
    """Build one session's complete JPEG from its spatial shards.

    Shards are joined with restart markers (RST0..RST7 cycling): each shard
    was packed with fresh DC predictors, each is 1-padded to a byte boundary
    and 0xFF-stuffed, which is precisely the restart-interval contract — so
    assembly is pure byte concatenation, no bit-level stitching.
    """
    from ..bitstream import jpeg_huffman  # noqa: F401  (tables type)
    from ..models.mjpeg import JpegEncoder
    from ..ops import bitpack

    nx = len(packed_shards)
    mcu_w = width // 16
    mcu_rows_per_shard = (height // 16) // nx
    enc = JpegEncoder(width, height, quality=quality, entropy="python")
    enc._tables = tables
    restart_interval = mcu_w * mcu_rows_per_shard if nx > 1 else 0

    parts = [enc._headers(tables, restart_interval=restart_interval)]
    for i, (shard, nbits) in enumerate(zip(packed_shards, totals)):
        scan = bitpack.finalize_bytes(shard, int(nbits), pad_bit=1)
        parts.append(bitpack.jpeg_stuff_bytes(scan))
        if i < nx - 1:
            parts.append(bytes([0xFF, 0xD0 + (i % 8)]))
    parts.append(b"\xff\xd9")
    return b"".join(parts)


# ---------------------------------------------------------------------------
# H.264 multi-session batch encode (the flagship codec over the mesh)
# ---------------------------------------------------------------------------

def h264_batch_encode_step(mesh: Mesh, frame_h: int, frame_w: int,
                           qp: int = 26, with_recon: bool = False):
    """Build the jitted multi-session H.264 CAVLC batch step for this mesh.

    Axes as in :func:`batch_encode_step`; the spatial split leans on the
    codec's slice-per-MB-row design (ops/h264_device): a contiguous block
    of MB rows is a self-contained set of slices (prediction never crosses
    rows), so each spatial shard runs the full device CAVLC stage on its
    row block with the right absolute ``first_mb`` slice headers, and a
    session's access unit is the in-order concatenation of its shards'
    NALs — no bit-level stitching, mirroring the JPEG restart-marker trick.

    Returns (step, hdr_vals, hdr_lens) where
      step(y, cb, cr) -> (flat_shards,): y (S, H, W) uint8 etc., S sharded
      over "session", H over "spatial"; flat_shards (S, nx, flat_len)
      uint8 — each row a shard's flat metadata+bitstream buffer.
    """
    from ..ops import cavlc_device

    ns, nx = mesh.devices.shape
    assert frame_h % (16 * nx) == 0, "MB rows must split across spatial axis"
    assert frame_w % 16 == 0
    nr, nc = frame_h // 16, frame_w // 16
    rows_local = nr // nx

    # Two header-slot sets so callers can alternate idr_pic_id between
    # consecutive IDR AUs (H.264 7.4.3 requires consecutive IDR pictures
    # to differ); same shapes, so no extra jit specialization.
    slots = []
    for pid in (0, 1):
        hv, hl = cavlc_device.slice_header_slots(
            nr, nc, frame_num=0, idr_pic_id=pid)
        slots.append((jnp.asarray(hv), jnp.asarray(hl)))

    def shard_fn(y, cb, cr, hv_l, hl_l):
        # y: (S/ns, H/nx, W); hv_l: (R/nx, SLOTS) — this shard's rows.
        def one(yy, cc, rr):
            return cavlc_device.encode_intra_cavlc_frame_yuv.__wrapped__(
                yy, cc, rr, hv_l, hl_l, qp, with_recon=with_recon)
        if with_recon:
            flat, recon = jax.vmap(one)(y, cb, cr)
            gathered = jnp.swapaxes(
                jax.lax.all_gather(flat, axis_name="spatial"), 0, 1)
            return (gathered,) + tuple(recon)
        flat = jax.vmap(one)(y, cb, cr)                 # (S_l, flat_len)
        return jnp.swapaxes(
            jax.lax.all_gather(flat, axis_name="spatial"), 0, 1)

    shard_spec = P("session", "spatial", None)
    out_specs = ((P("session", None, None),) + (shard_spec,) * 3
                 if with_recon else P("session", None, None))
    step = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(shard_spec, shard_spec, shard_spec,
                  P("spatial", None), P("spatial", None)),
        out_specs=out_specs,
        # check_vma=False: VMA checking rejects the replicated-out
        # psum/all_gather results these specs declare (jax 0.9 behavior);
        # re-enable when upstream accepts collective-produced replication
        check_vma=False,
    ))

    timed = _timed_step(step, "h264_intra")

    def run(y, cb, cr, idr_parity: int = 0):
        hv, hl = slots[idr_parity & 1]
        return timed(y, cb, cr, hv, hl)

    return run, rows_local


def assemble_session_h264(flat_shards: np.ndarray, rows_local: int,
                          headers: bytes = b"", nal_type: int = None,
                          ref_idc: int = 3) -> bytes:
    """One session's Annex-B access unit from its spatial shards."""
    from ..ops import cavlc_device

    parts = [headers]
    for shard in flat_shards:
        buf = np.asarray(shard)
        meta = cavlc_device.FlatMeta(buf, rows_local)
        assert not meta.overflow, "static cap overflow in batch encode"
        parts.append(cavlc_device.assemble_annexb(
            buf, meta, nal_type=nal_type, ref_idc=ref_idc))
    return b"".join(parts)


# ---------------------------------------------------------------------------
# Context-parallel P-frame batch encode: halo exchange over the spatial axis
# ---------------------------------------------------------------------------

def p_halo_feasible(frame_h: int, nx: int) -> bool:
    """True when every spatial shard is tall enough to donate the chroma
    halo the P step's motion window needs (single source of the rule)."""
    from ..ops.h264_inter import _PAD

    rows_local = (frame_h // 16) // max(nx, 1)
    return nx == 1 or 8 * rows_local >= _PAD


def h264_p_batch_step(mesh: Mesh, frame_h: int, frame_w: int, qp: int = 26,
                      deblock: bool = False):
    """Build the jitted multi-session **P-frame** batch step.

    The motion search window reaches up to ``_PAD`` (12) luma rows beyond a
    spatial shard's block of MB rows, so each shard first exchanges a
    12-row **halo** of the reference planes with its mesh neighbors via
    ``lax.ppermute`` (ICI point-to-point) — the honest context-parallel
    analog SURVEY.md §5 calls for: the sharded encode is then
    byte-identical to a monolithic one, because
    :func:`..ops.h264_inter.encode_p_frame_padded_ref` cannot tell halo
    rows from edge padding.

    Returns (step, rows_local) where
      step(y, cb, cr, ref_y, ref_cb, ref_cr, hv, hl)
        -> (flat_shards (S, nx, L), new_ref_y, new_ref_cb, new_ref_cr)
    with frames AND references sharded (session, spatial) and the returned
    references staying sharded on device for the next step.

    ``deblock=True`` runs the normative in-loop filter on each shard's
    row block before it becomes the next reference — the round-6
    wavefront deblock SPLIT ACROSS THE SPATIAL MESH AXIS: under
    slice-per-row (idc=2) the filter never crosses MB-row boundaries,
    so per-shard filtering of a contiguous row block is byte-identical
    to filtering the assembled frame, and the two long column scans'
    cost divides over the mesh with zero extra halo traffic.
    """
    from ..ops import cavlc_p_device, h264_deblock
    from ..ops.h264_inter import _PAD

    ns, nx = mesh.devices.shape
    assert frame_h % (16 * nx) == 0, "MB rows must split across spatial axis"
    assert frame_w % 16 == 0
    nr, nc = frame_h // 16, frame_w // 16
    rows_local = nr // nx
    # chroma halo needs _PAD rows from a shard of height 8*rows_local
    assert p_halo_feasible(frame_h, nx), \
        f"need >= {-(-_PAD // 8)} MB rows per spatial shard for the halo"

    perm_down = [(i, i + 1) for i in range(nx - 1)]   # data to shard below
    perm_up = [(i + 1, i) for i in range(nx - 1)]     # data to shard above

    def halo_pad(ref):
        """(S_l, h_l, w) sharded ref -> (S_l, h_l+2P, w+2P) padded with
        neighbor halos (interior seams) / edge replication (frame edges)."""
        if nx == 1:
            return jnp.pad(ref, ((0, 0), (_PAD, _PAD), (_PAD, _PAD)),
                           mode="edge")
        top_halo = jax.lax.ppermute(ref[:, -_PAD:], "spatial", perm_down)
        bot_halo = jax.lax.ppermute(ref[:, :_PAD], "spatial", perm_up)
        ax = jax.lax.axis_index("spatial")
        edge_top = jnp.repeat(ref[:, :1], _PAD, axis=1)
        edge_bot = jnp.repeat(ref[:, -1:], _PAD, axis=1)
        top = jnp.where(ax == 0, edge_top, top_halo)
        bot = jnp.where(ax == nx - 1, edge_bot, bot_halo)
        rows = jnp.concatenate([top, ref, bot], axis=1)
        return jnp.pad(rows, ((0, 0), (0, 0), (_PAD, _PAD)), mode="edge")

    def shard_fn(y, cb, cr, ry, rcb, rcr, hv_l, hl_l):
        ry_pad = halo_pad(ry.astype(jnp.int32))
        rcb_pad = halo_pad(rcb.astype(jnp.int32))
        rcr_pad = halo_pad(rcr.astype(jnp.int32))

        def one(yy, cc, rr, ryp, rcbp, rcrp):
            flat, ny, ncb, ncr, mv, nnz, _lv = \
                cavlc_p_device.encode_p_cavlc_frame_padded(
                    yy, cc, rr, ryp, rcbp, rcrp, hv_l, hl_l, qp)
            if deblock:
                ny, ncb, ncr = h264_deblock.deblock_frame.__wrapped__(
                    ny, ncb, ncr, qp, nnz_blk=nnz, mv=mv)
            return flat, ny, ncb, ncr

        flat, ny, ncb, ncr = jax.vmap(one)(
            y, cb, cr, ry_pad, rcb_pad, rcr_pad)
        flat_all = jnp.swapaxes(
            jax.lax.all_gather(flat, axis_name="spatial"), 0, 1)
        return flat_all, ny, ncb, ncr

    step = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("session", "spatial", None),) * 6
                 + (P("spatial", None), P("spatial", None)),
        out_specs=(P("session", None, None),
                   P("session", "spatial", None),
                   P("session", "spatial", None),
                   P("session", "spatial", None)),
        # check_vma=False: VMA checking rejects the replicated-out
        # psum/all_gather results these specs declare (jax 0.9 behavior);
        # re-enable when upstream accepts collective-produced replication
        check_vma=False,
    ))
    return _timed_step(step, "h264_p"), rows_local


def h264_p_chunk_batch_step(mesh: Mesh, frame_h: int, frame_w: int,
                            chunk: int, qp: int = 26,
                            deblock: bool = False):
    """Multi-session GOP-chunk SUPER-STEP over the mesh (ROADMAP item 2
    at fleet scale): ``chunk`` P frames for every session encode in ONE
    jitted shard_map program — a ``lax.scan`` over the frame axis with
    the per-frame halo exchange (``ppermute``) and the sharded deblock
    INSIDE the scan body, so the host pays one dispatch per chunk per
    bucket instead of per tick.

    The sharded reference planes are donated and returned under the
    IDENTICAL ``P("session", "spatial", None)`` spec they came in with
    (the SNIPPETS.md [1]/[3] pjit contract: out specs of call N == in
    specs of call N+1), so chained chunk calls alias the reference ring
    in place and never repartition.

    Returns (step, rows_local) where
      step(ys, cbs, crs, ref_y, ref_cb, ref_cr, hv, hl)
        -> (flat_shards (S, K, nx, L), ref_y', ref_cb', ref_cr')
    with ``ys`` (S, K, H, W) — session-sharded, frame axis unsharded,
    rows sharded over "spatial" — and ``hv``/``hl`` the K frames'
    header slots stacked on axis 0 (rows sharded over "spatial").
    Byte-identical to ``chunk`` consecutive :func:`h264_p_batch_step`
    calls (tested GOP-deep in tests/test_superstep.py).
    """
    from ..ops import cavlc_p_device, h264_deblock
    from ..ops.h264_inter import _PAD

    ns, nx = mesh.devices.shape
    assert frame_h % (16 * nx) == 0, "MB rows must split across spatial axis"
    assert frame_w % 16 == 0
    nr = frame_h // 16
    rows_local = nr // nx
    assert p_halo_feasible(frame_h, nx), \
        f"need >= {-(-_PAD // 8)} MB rows per spatial shard for the halo"

    perm_down = [(i, i + 1) for i in range(nx - 1)]
    perm_up = [(i + 1, i) for i in range(nx - 1)]

    def halo_pad(ref):
        if nx == 1:
            return jnp.pad(ref, ((0, 0), (_PAD, _PAD), (_PAD, _PAD)),
                           mode="edge")
        top_halo = jax.lax.ppermute(ref[:, -_PAD:], "spatial", perm_down)
        bot_halo = jax.lax.ppermute(ref[:, :_PAD], "spatial", perm_up)
        ax = jax.lax.axis_index("spatial")
        edge_top = jnp.repeat(ref[:, :1], _PAD, axis=1)
        edge_bot = jnp.repeat(ref[:, -1:], _PAD, axis=1)
        top = jnp.where(ax == 0, edge_top, top_halo)
        bot = jnp.where(ax == nx - 1, edge_bot, bot_halo)
        rows = jnp.concatenate([top, ref, bot], axis=1)
        return jnp.pad(rows, ((0, 0), (0, 0), (_PAD, _PAD)), mode="edge")

    def shard_fn(ys, cbs, crs, ry, rcb, rcr, hv, hl):
        # ys: (S_l, K, h_l, w) local shard; scan over the frame axis
        def body(carry, xs):
            ry, rcb, rcr = carry
            y, cb, cr, hv_f, hl_f = xs
            ry_pad = halo_pad(ry.astype(jnp.int32))
            rcb_pad = halo_pad(rcb.astype(jnp.int32))
            rcr_pad = halo_pad(rcr.astype(jnp.int32))

            def one(yy, cc, rr, ryp, rcbp, rcrp):
                flat, ny, ncb, ncr, mv, nnz, _lv = \
                    cavlc_p_device.encode_p_cavlc_frame_padded(
                        yy, cc, rr, ryp, rcbp, rcrp, hv_f, hl_f, qp)
                if deblock:
                    ny, ncb, ncr = h264_deblock.deblock_frame.__wrapped__(
                        ny, ncb, ncr, qp, nnz_blk=nnz, mv=mv)
                return flat, ny, ncb, ncr

            flat, ny, ncb, ncr = jax.vmap(one)(
                y, cb, cr, ry_pad, rcb_pad, rcr_pad)
            flat_all = jnp.swapaxes(
                jax.lax.all_gather(flat, axis_name="spatial"), 0, 1)
            return (ny, ncb, ncr), flat_all

        frames = tuple(jnp.swapaxes(a, 0, 1) for a in (ys, cbs, crs))
        (ry, rcb, rcr), flats = jax.lax.scan(
            body, (ry, rcb, rcr), frames + (hv, hl))
        # (K, S_l, nx, L) -> (S_l, K, nx, L): session-major like the
        # per-frame step, frame axis inside
        return jnp.swapaxes(flats, 0, 1), ry, rcb, rcr

    ref_spec = P("session", "spatial", None)
    step = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("session", None, "spatial", None),) * 3
                 + (ref_spec,) * 3
                 + (P(None, "spatial", None), P(None, "spatial", None)),
        out_specs=(P("session", None, None, None),
                   ref_spec, ref_spec, ref_spec),
        # check_vma=False: VMA checking rejects the replicated-out
        # all_gather results these specs declare (jax 0.9 behavior)
        check_vma=False,
    ), donate_argnums=(3, 4, 5))
    return _timed_step(step, "h264_p_chunk"), rows_local


# ---------------------------------------------------------------------------
# Single-session spatial sharding: ONE frame's MB rows across N chips
#
# The batch steps above shard a *population* of sessions; these shard a
# *single* session's frame — the TurboServe economics (PAPERS.md): a
# session that cannot hit its SLO on one chip transparently consumes
# several.  Same substrate: slice-per-MB-row makes a contiguous block of
# rows a self-contained set of slices, the ME search window crosses the
# shard seam through the ppermute reference halo, the in-loop deblock
# splits per shard under idc=2, and entropy is emitted per shard —
# CAVLC flat buffers concatenated NAL-by-NAL, CABAC binarize record
# streams (per-row independent by construction, ops/cabac_binarize)
# stitched row-wise on the host (ops.cabac_binarize.stitch_rows) — so
# the assembled AU is byte-identical to the single-device path.
# ---------------------------------------------------------------------------

def make_spatial_mesh(nx: int, devices=None) -> Mesh:
    """A (1, nx) ("session", "spatial") mesh for one spatially-sharded
    session — the single-session degenerate of :func:`make_mesh`."""
    devices = jax.devices() if devices is None else devices
    return make_mesh((1, nx), devices[:nx])


def coded_height(height: int, nx: int = 1) -> int:
    """Lines of the CODED picture of a ``height``-line display whose MB
    rows ``nx`` spatial shards divide: the next multiple of ``16 * nx``
    (edge rows repeated, the SPS crops them back), at most ``nx - 1`` MB
    rows more than a one-chip encoder codes."""
    step = 16 * max(int(nx), 1)
    return -(-int(height) // step) * step


def feasible_spatial_shards(height: int, want: int,
                            n_devices: int) -> int:
    """Clamp a requested spatial shard count to what the geometry
    supports.  The coded height FOLLOWS the mesh (:func:`coded_height`:
    the picture is padded to a multiple of ``16 * nx`` lines and
    cropped back by the SPS), so the MB rows always divide; what is
    left of the rule is that each shard is tall enough to donate the P
    halo and that no shard is padding alone.  Prefers the smallest
    feasible count >= ``want`` (enough chips to close the budget), else
    the largest feasible one below it.  Native 4K (135 MB rows) on a
    four-chip host: 4 shards of 34 rows, one padding row of 136
    (``desk2160-cabac-mesh4``)."""
    want = max(int(want), 1)
    rows = -(-int(height) // 16)

    def feasible(n: int) -> bool:
        coded = coded_height(height, n)
        return (p_halo_feasible(coded, n)
                and coded // 16 - rows < max(coded // 16 // n, 1))

    cands = [n for n in range(1, max(int(n_devices), 1) + 1)
             if feasible(n)]
    up = [n for n in cands if n >= want]
    return min(up) if up else max(cands)


def _spatial_halo_pad(nx: int):
    """Per-shard reference padding for a SINGLE session's (h_l, w)
    planes: ``_PAD`` rows of neighbor halo over ``ppermute`` at interior
    seams, edge replication at frame edges.  The rows cross in the
    plane's own dtype (uint8 references: a quarter of the int32 the
    search reads) under the scope ``dngd.halo``."""
    from ..ops.h264_inter import _PAD

    perm_down = [(i, i + 1) for i in range(nx - 1)]
    perm_up = [(i + 1, i) for i in range(nx - 1)]

    def pad(ref):
        if nx == 1:
            return jnp.pad(ref, ((_PAD, _PAD), (_PAD, _PAD)),
                           mode="edge")
        with jax.named_scope("dngd.halo"):
            top_halo = jax.lax.ppermute(ref[-_PAD:], "spatial", perm_down)
            bot_halo = jax.lax.ppermute(ref[:_PAD], "spatial", perm_up)
            ax = jax.lax.axis_index("spatial")
            edge_top = jnp.repeat(ref[:1], _PAD, axis=0)
            edge_bot = jnp.repeat(ref[-1:], _PAD, axis=0)
            top = jnp.where(ax == 0, edge_top, top_halo)
            bot = jnp.where(ax == nx - 1, edge_bot, bot_halo)
            rows = jnp.concatenate([top, ref, bot], axis=0)
            return jnp.pad(rows, ((0, 0), (_PAD, _PAD)), mode="edge")

    return pad


#: The per-frame steps' entropy buffers, ``(nx, L)`` with row ``i`` on
#: chip ``i``: the host pulls a guessed prefix of each shard's buffer from
#: its own chip.  Not an ``all_gather`` to every chip: compiled for a v5e
#: 2x2 that is an all-reduce over ``nx`` x 36 MB a 4K frame, to spare the
#: host three pulls of under a megabyte (PERF.md section 6, PR 36).  The
#: chunk step below still gathers (its ring hands the host ``K`` frames).
_SHARD_BUF_SPEC = P("spatial", None)


def _gather_shards(buf):
    """Every shard's entropy buffer on every chip (scope
    ``dngd.gather``): the host then pulls one array."""
    with jax.named_scope("dngd.gather"):
        return jax.lax.all_gather(buf, axis_name="spatial")


def spatial_halo_bytes(frame_w: int, nx: int, itemsize: int = 1) -> int:
    """Bytes the reference halo brings ONE chip a P frame: ``_PAD`` luma
    rows and ``_PAD`` rows of each chroma plane from each neighbour (two
    for an interior shard, the most any chip receives; one on a
    two-chip mesh)."""
    from ..ops.h264_inter import _PAD
    return (min(max(nx - 1, 0), 2) * _PAD * (frame_w + 2 * (frame_w // 2))
            * itemsize)


# P-path levels dict keys (ops/cavlc_p_device._finish_p contract): the
# host-entropy overflow fallback's tensors, returned lazily sharded.
_P_LEVEL_KEYS = ("luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")


def _spatial_specs(mesh):
    """(plane_spec, row_spec) for single-session arrays on a (1, nx)
    spatial mesh: planes shard their leading (row) axis, everything
    else is unsharded."""
    del mesh
    return P("spatial", None), P("spatial", None)


def h264_spatial_intra_step(mesh: Mesh, frame_h: int, frame_w: int,
                            qp: int = 26, entropy: str = "cavlc",
                            i16_modes: str = "auto",
                            deblock: bool = False,
                            with_recon: bool = True,
                            tune: str = "off"):
    """Build the jitted single-session SPATIAL intra step: one frame's
    MB rows split over the mesh's "spatial" axis.

    Returns (step, rows_local):
      - entropy="cavlc":  step(y, cb, cr, hv, hl) ->
        (flat_shards (nx, L)[, recon_y, recon_cb, recon_cr]) with the
        recon staying SHARDED on device (``P("spatial", None)``) as the
        P chain's reference ring, and each shard's entropy buffer on
        its own chip (row ``i`` of ``flat_shards`` lives on chip ``i``:
        the host pulls a guessed prefix of each, :data:`_SHARD_BUF_SPEC`).
      - entropy="cabac":  step(y, cb, cr) ->
        (rec_shards (nx, Lb)[, recon...], levels) — per-shard
        cabac_binarize record streams (stitched host-side) plus the
        lazy level tensors the dense overflow fallback needs.

    ``qp=None`` (tune="off" only) builds the qp-TRACED program, as the
    one-chip ``_dynqp`` twins are built: the step takes the slice qp as
    one more, replicated, int32 operand after the others, so one compile
    serves the whole rate ladder.  The compiled program is named
    ``jit_encode_intra_mesh``: a frame, to the benchmark's reductions,
    is an execution of a program whose name starts with ``jit_encode_``.

    ``deblock`` loop-filters each shard's recon before it becomes the
    reference (byte-identical to whole-frame filtering under idc=2).
    """
    from ..ops import cabac_binarize, cavlc_device, h264_deblock
    from ..ops import h264_device

    ns, nx = mesh.devices.shape
    assert ns == 1, "spatial steps serve ONE session (use the batch " \
                    "steps for populations)"
    assert frame_h % (16 * nx) == 0, "MB rows must split across shards"
    assert frame_w % 16 == 0
    # per-MB AQ (ops/aq) is a pure per-MB function and the mb_qp_delta
    # chain is per-row, so a sharded tune=hq frame is byte-identical to
    # the single-device one; the CABAC binarize records have no qp
    # plumbing yet, so that pairing is rejected here (models/h264 routes
    # hq+cabac through the dense host path instead)
    assert not (tune == "hq" and entropy == "cabac"), \
        "tune=hq has no device-binarize qp plumbing (use dense CABAC)"
    assert entropy in ("cavlc", "cabac"), \
        f"unknown spatial entropy {entropy!r}"
    dyn = qp is None
    assert not dyn or tune == "off", "a traced qp needs tune='off'"
    static_qp = qp
    rows_local = (frame_h // 16) // nx
    plane_spec, row_spec = _spatial_specs(mesh)
    buf_spec = _SHARD_BUF_SPEC
    qp_spec = (P(),) if dyn else ()

    if entropy == "cavlc":
        def encode_intra_mesh(y, cb, cr, hv_l, hl_l, *qp_t):
            qp = qp_t[0] if dyn else static_qp
            out = cavlc_device.encode_intra_cavlc_frame_yuv.__wrapped__(
                y, cb, cr, hv_l, hl_l, qp, with_recon=with_recon,
                i16_modes=i16_modes, tune=tune)
            if with_recon:
                flat, recon = out
            else:
                flat, recon = out, ()
            if with_recon and deblock:
                recon = h264_deblock.deblock_frame.__wrapped__(
                    *recon, qp)
            if not with_recon:
                return flat[None]
            return (flat[None],) + tuple(recon)

        in_specs = (plane_spec,) * 3 + (row_spec,) * 2 + qp_spec
        out_specs = ((buf_spec,) + (plane_spec,) * 3
                     if with_recon else buf_spec)
    else:
        def encode_intra_mesh(y, cb, cr, *qp_t):
            qp = qp_t[0] if dyn else static_qp
            lv = h264_device.encode_intra_frame_yuv.__wrapped__(
                y, cb, cr, qp, i16_modes, tune)
            buf = cabac_binarize.binarize_intra.__wrapped__(
                lv["luma_dc"], lv["luma_ac"], lv["cb_dc"], lv["cb_ac"],
                lv["cr_dc"], lv["cr_ac"], lv["pred_mode"], lv["mb_i4"],
                lv["i4_modes"], lv["luma_i4"])
            recon = (lv["recon_y"], lv["recon_cb"], lv["recon_cr"])
            if deblock:
                recon = h264_deblock.deblock_frame.__wrapped__(*recon, qp)
            small = {k: v for k, v in lv.items()
                     if not k.startswith("recon")}
            if with_recon:
                return (buf[None],) + tuple(recon) + (small,)
            return buf[None], small

        lv_spec = jax.tree_util.tree_map(
            lambda _: P("spatial"),
            {k: 0 for k in ("luma_dc", "luma_ac", "cb_dc", "cb_ac",
                            "cr_dc", "cr_ac", "pred_mode", "mb_i4",
                            "i4_modes", "luma_i4")})
        in_specs = (plane_spec,) * 3 + qp_spec
        out_specs = ((buf_spec,)
                     + ((plane_spec,) * 3 if with_recon else ())
                     + (lv_spec,))
    step = jax.jit(shard_map(
        encode_intra_mesh, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))
    return _timed_step(step, "h264_sp_intra"), rows_local


def _spatial_encode_frame(entropy: str, deblock: bool, qp: int,
                          halo_pad, tune: str = "off",
                          p_intra: bool = False):
    """The per-shard P-frame body BOTH spatial builders run (the
    per-frame step and the chunk scan — one implementation, so the
    chunk-vs-per-frame byte identity cannot drift): halo-pad the refs,
    ME/MC + entropy per shard, optional per-shard deblock.  Returns
    fn(y, cb, cr, ry, rcb, rcr, hv_f, hl_f, next_y=None, keep=None) ->
    (flat, ny, ncb, ncr, mv, levels).  ``tune``/``next_y``: the
    ENCODER_TUNE=hq axis — per-MB, so shard-safe by construction.
    A caller whose qp is a traced operand (tune="off") hands it to
    ``fn(..., qp=...)`` in place of the closed-over constant.

    ``keep`` (cavlc only) is the damage mask's per-local-row gate
    (ops/damage_mask.force_skip_rows): rows where ``keep`` is False are
    forced to all-P_Skip BEFORE entropy and their recon frozen to the
    reference.  The shard cannot COMPACT its worklist (that would
    repartition the shard_map), so masked spatial trades no ME cycles —
    it gates the bitstream and the recon chain, keeping the sharded
    stream byte-conformant with the compacted single-device paths."""
    from ..ops import cabac_binarize, cavlc_p_device, h264_deblock
    from ..ops import h264_inter
    from ..ops.h264_device import nnz_blocks_raster

    assert not (tune == "hq" and entropy == "cabac"), \
        "tune=hq has no device-binarize qp plumbing (use dense CABAC)"
    assert not (p_intra and (entropy != "cavlc" or deblock)), \
        "p_intra requires cavlc entropy, deblock off"

    def encode_one(y, cb, cr, ry, rcb, rcr, hv_f, hl_f, next_y=None,
                   keep=None, qp=qp):
        # the halo rows cross the mesh as the references' own uint8
        ry_pad = halo_pad(ry).astype(jnp.int32)
        rcb_pad = halo_pad(rcb).astype(jnp.int32)
        rcr_pad = halo_pad(rcr).astype(jnp.int32)
        if entropy == "cavlc":
            if keep is not None:
                # decomposed fused stage: inter core -> forced-skip row
                # gate -> entropy finish (the fused call IS core+finish,
                # so the unmasked bytes cannot drift)
                from ..ops import damage_mask
                out = h264_inter.encode_p_frame_padded_ref(
                    y, cb, cr, ry_pad, rcb_pad, rcr_pad, qp, tune=tune,
                    next_y=next_y, p_intra=p_intra)
                out = damage_mask.force_skip_rows(out, keep, ry, rcb,
                                                  rcr)
                flat, ny, ncb, ncr, mv, nnz, lv = \
                    cavlc_p_device._finish_p(out, hv_f, hl_f,
                                             slice_qp=qp)
            else:
                flat, ny, ncb, ncr, mv, nnz, lv = \
                    cavlc_p_device.encode_p_cavlc_frame_padded(
                        y, cb, cr, ry_pad, rcb_pad, rcr_pad,
                        hv_f, hl_f, qp, tune=tune, next_y=next_y,
                        p_intra=p_intra)
        else:
            out = h264_inter.encode_p_frame_padded_ref(
                y, cb, cr, ry_pad, rcb_pad, rcr_pad, qp, tune=tune,
                next_y=next_y)
            ny, ncb, ncr = (out["recon_y"], out["recon_cb"],
                            out["recon_cr"])
            mv = out["mv"]
            nnz = nnz_blocks_raster(out["luma"])
            flat = cabac_binarize.binarize_p.__wrapped__(
                out["mv"], out["luma"], out["cb_dc"], out["cb_ac"],
                out["cr_dc"], out["cr_ac"])
            lv = {k: out[k] for k in _P_LEVEL_KEYS}
        if deblock:
            ny, ncb, ncr = h264_deblock.deblock_frame.__wrapped__(
                ny, ncb, ncr, qp, nnz_blk=nnz,
                mv=mv.astype(jnp.int32))
        return flat, ny, ncb, ncr, mv, lv

    return encode_one


def h264_spatial_step(mesh: Mesh, frame_h: int, frame_w: int,
                      qp: int = 26, deblock: bool = False,
                      entropy: str = "cavlc", tune: str = "off",
                      p_intra: bool = False, masked: bool = False):
    """Build the jitted single-session SPATIAL **P** step (the tentpole
    kernel): ME/MC with the reference halo exchanged over ``ppermute``,
    per-shard in-loop deblock, per-shard entropy.

    Returns (step, rows_local):
      - entropy="cavlc":  step(y, cb, cr, ry, rcb, rcr, hv, hl) ->
        (flat_shards (nx, L), ry', rcb', rcr', mv, levels)
      - entropy="cabac":  step(y, cb, cr, ry, rcb, rcr) ->
        (rec_shards (nx, Lb), ry', rcb', rcr', mv, levels)
    with references consumed/returned SHARDED under the identical
    ``P("spatial", None)`` spec (ring contract), ``mv``/``levels``
    lazy for the overflow fallback.  ``qp=None`` (tune="off" only): the
    slice qp is one more, replicated, int32 operand after the others
    (:func:`h264_spatial_intra_step`); the compiled program is named
    ``jit_encode_p_mesh``.
    """
    ns, nx = mesh.devices.shape
    assert ns == 1, "spatial steps serve ONE session"
    assert frame_h % (16 * nx) == 0, "MB rows must split across shards"
    assert frame_w % 16 == 0
    assert p_halo_feasible(frame_h, nx), "shards too short for the halo"
    assert entropy in ("cavlc", "cabac"), \
        f"unknown spatial entropy {entropy!r}"
    dyn = qp is None
    assert not dyn or tune == "off", "a traced qp needs tune='off'"
    rows_local = (frame_h // 16) // nx
    plane_spec, row_spec = _spatial_specs(mesh)
    lv_keys = _P_LEVEL_KEYS + (("qp_map",) if tune == "hq" else ())
    if p_intra:
        lv_keys = lv_keys + ("mb_intra", "i16_dc", "i16_ac")
    lv_spec = {k: P("spatial") for k in lv_keys}
    encode_one = _spatial_encode_frame(entropy, deblock, qp,
                                       _spatial_halo_pad(nx), tune=tune,
                                       p_intra=p_intra)
    # operands after the six planes: the header slots (cavlc), the
    # damage mask's row gate (a separate build, so that the unmasked
    # program and its bytes are untouched: rows gated False emit as
    # pure skip runs with their recon frozen, ops/damage_mask), the
    # traced qp
    masked = masked and entropy == "cavlc"
    n_hdr = 2 if entropy == "cavlc" else 0
    in_specs = ((plane_spec,) * 6 + (row_spec,) * n_hdr
                + ((P("spatial"),) if masked else ())
                + ((P(),) if dyn else ()))

    def encode_p_mesh(y, cb, cr, ry, rcb, rcr, *rest):
        hv_l, hl_l = rest[:n_hdr] if n_hdr else (None, None)
        kw = {"keep": rest[n_hdr]} if masked else {}
        if dyn:
            kw["qp"] = rest[-1]
        flat, ny, ncb, ncr, mv, lv = encode_one(
            y, cb, cr, ry, rcb, rcr, hv_l, hl_l, **kw)
        return flat[None], ny, ncb, ncr, mv, lv

    step = jax.jit(shard_map(
        encode_p_mesh, mesh=mesh,
        in_specs=in_specs,
        out_specs=(_SHARD_BUF_SPEC, plane_spec, plane_spec, plane_spec,
                   P("spatial"), lv_spec),
        check_vma=False,
    ))
    return _timed_step(step, "h264_sp_p"), rows_local


def h264_spatial_chunk_step(mesh: Mesh, qp: int = 26,
                            deblock: bool = False,
                            entropy: str = "cavlc",
                            prefix_len: int = 0,
                            tune: str = "off", p_intra: bool = False):
    """Single-session SPATIAL GOP-chunk super-step: the PR 8 donated
    ring-buffer scan grown a spatial axis — ``K`` P frames of ONE
    session encode in one jitted shard_map program, the per-frame halo
    exchange and sharded deblock INSIDE the scan body, the sharded
    reference ring donated (under the :data:`ops.h264_inter.RING_DONATE`
    gate) and returned under the identical ``P("spatial", None)`` spec
    so chained chunks alias in place and never repartition
    (SNIPPETS.md [1]/[3] pjit contract).

    Shape-specialized per (chunk, geometry) like
    :func:`ops.devloop.build_p_chunk_step` (which delegates here under
    ``spatial_shards > 1``); same 7-tuple return so the serving ring
    (models/h264) consumes either transparently:

      step(ys (K,H,W), cbs, crs, ref_y, ref_cb, ref_cr, hv, hl) ->
        (flats (K, nx, L), prefix, ref_y', ref_cb', ref_cr', mvs,
         levels)
    with ``hv``/``hl`` the K frames' header slots stacked on axis 0
    (cavlc; ignored under cabac — the host engine writes headers).
    """
    ns, nx = mesh.devices.shape
    assert ns == 1, "spatial steps serve ONE session"
    if entropy not in ("cavlc", "cabac"):
        raise ValueError(f"unknown spatial chunk entropy {entropy!r}")
    plane_spec, _ = _spatial_specs(mesh)
    frame_spec = P(None, "spatial", None)
    lv_keys = _P_LEVEL_KEYS + (("qp_map",) if tune == "hq" else ())
    if p_intra:
        lv_keys = lv_keys + ("mb_intra", "i16_dc", "i16_ac")
    lv_spec = {k: P(None, "spatial") for k in lv_keys}
    # the scan body IS the per-frame spatial step's body (one shared
    # implementation — the chunk-vs-per-frame byte identity the tests
    # pin cannot drift between two copies)
    encode_one = _spatial_encode_frame(entropy, deblock, qp,
                                       _spatial_halo_pad(nx), tune=tune,
                                       p_intra=p_intra)

    def scan_chunk(ys, cbs, crs, ry, rcb, rcr, hv, hl):
        def body(carry, xs):
            ry, rcb, rcr = carry
            next_y = None
            if entropy == "cavlc":
                if tune == "hq":
                    y, cb, cr, hv_f, hl_f, next_y = xs
                else:
                    y, cb, cr, hv_f, hl_f = xs
            else:
                if tune == "hq":
                    (y, cb, cr, next_y), hv_f, hl_f = xs, None, None
                else:
                    (y, cb, cr), hv_f, hl_f = xs, None, None
            flat, ny, ncb, ncr, mv, lv = encode_one(
                y, cb, cr, ry, rcb, rcr, hv_f, hl_f, next_y=next_y)
            flat_all = _gather_shards(flat)
            return (ny, ncb, ncr), (flat_all, mv, lv)

        xs = ((ys, cbs, crs, hv, hl) if entropy == "cavlc"
              else (ys, cbs, crs))
        if tune == "hq":
            # 1-frame lookahead from the ring's already-staged frames:
            # frame k pre-biases its qp plane with frame k+1's luma (the
            # last frame sees itself — the full static bias, mirrored by
            # the ring-flush path); per-shard rows, so identical to the
            # single-device chunk's shift
            xs = xs + (jnp.concatenate([ys[1:], ys[-1:]], axis=0),)
        (ry, rcb, rcr), (flats, mvs, lvs) = jax.lax.scan(
            body, (ry, rcb, rcr), xs)
        prefix = flats if prefix_len <= 0 else flats[:, :, :prefix_len]
        return flats, prefix, ry, rcb, rcr, mvs, lvs

    out_specs = (P(None, None, None), P(None, None, None),
                 plane_spec, plane_spec, plane_spec,
                 P(None, "spatial"), lv_spec)
    if entropy == "cavlc":
        shard_fn = scan_chunk
        in_specs = ((frame_spec,) * 3 + (plane_spec,) * 3
                    + (frame_spec, frame_spec))
    else:
        def shard_fn(ys, cbs, crs, ry, rcb, rcr):
            return scan_chunk(ys, cbs, crs, ry, rcb, rcr, None, None)

        in_specs = (frame_spec,) * 3 + (plane_spec,) * 3
    # ring donation honors the ONE switch the single-device chunk step
    # uses (ops/h264_inter.RING_DONATE: donated only on positive
    # device-platform evidence, because jaxlib's CPU client corrupted
    # the heap donating scan-carry rings, round 8 bisect).  Undonated,
    # the contract is merely slower — the
    # returned ring still re-enters under the same fixed spec.
    from ..ops.h264_inter import RING_DONATE
    step = jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ), donate_argnums=(3, 4, 5) if RING_DONATE else ())
    return _timed_step(step, "h264_sp_chunk")


def _assert_spread(arr, n: int, what: str) -> None:
    """``arr``'s shards sit on ``n`` DISTINCT devices — a mesh that
    quietly put everything on the first chip fails here."""
    devs = {s.device for s in arr.addressable_shards}
    assert len(devs) == n, (
        f"{what}: shards on {len(devs)} device(s) {sorted(map(str, devs))}, "
        f"want {n} distinct")


def dryrun_full_geometry(n_devices: int, h: int = 1088,
                         w: int = 1920, gop_p: int = 3) -> None:
    """BASELINE config-5 geometry proof (VERDICT r4 item 6): n full-HD
    sessions over an (n, 1) session mesh, per-session AU byte-equality
    vs the single-device encoder, peak host/device memory logged.  The
    toy-geometry dryrun proves the sharding program compiles; THIS
    proves the real-geometry memory footprint and the byte contract.

    Round 6 (VERDICT r5 item 7): a SHORT GOP follows — IDR + ``gop_p``
    P frames on an (n/2, 2) mesh so the spatial axis is live: reference
    halos cross chips via ppermute each frame AND the in-loop deblock
    runs per-shard (mesh-shared wavefront).  Every AU must stay
    byte-identical to the single-device encoder's, which proves halo
    rows are indistinguishable from monolithic padding and the sharded
    deblock from whole-frame filtering, GOP-deep."""
    import resource

    from ..models.h264 import H264Encoder
    from ..ops import cavlc_device

    devices = jax.devices()[:n_devices]
    mesh = make_mesh((n_devices, 1), devices)
    enc = H264Encoder(w, h, qp=26)                     # headers only
    rng = np.random.default_rng(7)
    # desktop-ish blocky YUV content (kron of an 8x coarse grid), one
    # shifted variant per session so every session codes distinct bytes.
    # Planes are synthesized directly — no cv2/RGB dependency, and both
    # the sharded step and the single-device reference consume the SAME
    # plane bytes, so the comparison is exact by construction.
    def plane(hh, ww, seed):
        c = rng.integers(0, 255, size=(hh // 8, ww // 8)).astype(np.uint8)
        return np.kron(c, np.ones((8, 8), np.uint8)).astype(np.uint8)

    ys = np.stack([np.roll(plane(h, w, s), 8 * s, axis=1)
                   for s in range(n_devices)])
    cbs = np.stack([np.roll(plane(h // 2, w // 2, s), 4 * s, axis=1)
                    for s in range(n_devices)])
    crs = np.stack([np.roll(plane(h // 2, w // 2, s), 4 * s, axis=1)
                    for s in range(n_devices)])
    step, rows_local = h264_batch_encode_step(mesh, h, w, qp=26)
    # inputs placed one session per chip, and the step's output must
    # come back from every chip — not all from the first
    on_mesh = NamedSharding(mesh, P("session", None, None))
    ys_d, cbs_d, crs_d = (jax.device_put(a, on_mesh)
                          for a in (ys, cbs, crs))
    _assert_spread(ys_d, n_devices, "(n,1) input luma")
    flat_d = step(ys_d, cbs_d, crs_d)
    _assert_spread(flat_d, n_devices, "(n,1) step output")
    flat = np.asarray(flat_d)
    assert flat.shape[0] == n_devices
    hv, hl = enc._hdr_slots(0, 0)
    sizes = []
    for s in range(n_devices):
        au = assemble_session_h264(flat[s], rows_local,
                                   headers=enc.headers())
        sflat = np.asarray(cavlc_device.encode_intra_cavlc_frame_yuv(
            jnp.asarray(ys[s]), jnp.asarray(cbs[s]), jnp.asarray(crs[s]),
            hv, hl, 26, with_recon=False))
        meta = cavlc_device.FlatMeta(sflat, h // 16)
        assert not meta.overflow
        want = cavlc_device.assemble_annexb(sflat, meta,
                                            headers=enc.headers())
        assert au == want, (
            f"session {s}: sharded 1080p AU diverges from single-device")
        sizes.append(len(au))
    # --- short GOP: IDR + P frames, live halo + mesh-shared deblock ----
    gop_info = ""
    if gop_p > 0 and n_devices >= 2:
        from ..bitstream import h264 as syn
        from ..ops import cavlc_p_device, h264_deblock

        ns_g, nx_g = n_devices // 2, 2
        assert p_halo_feasible(h, nx_g)
        mesh_g = make_mesh((ns_g, nx_g), jax.devices()[:ns_g * nx_g])
        qp = 26
        i_step, rows_l = h264_batch_encode_step(mesh_g, h, w, qp=qp,
                                                with_recon=True)
        flat_i, *ref_s = i_step(ys[:ns_g], cbs[:ns_g], crs[:ns_g])
        for r in ref_s:
            _assert_spread(r, ns_g * nx_g, "(n/2,2) IDR reference")
        flat_i = np.asarray(flat_i)
        # single-device twin: same IDR per session, host-held recon
        hv, hl = enc._hdr_slots(0, 0)
        ref_1 = []
        for s in range(ns_g):
            sflat, recon = cavlc_device.encode_intra_cavlc_frame_yuv(
                jnp.asarray(ys[s]), jnp.asarray(cbs[s]),
                jnp.asarray(crs[s]), hv, hl, qp, with_recon=True)
            au_s = assemble_session_h264(flat_i[s], rows_l,
                                         headers=enc.headers())
            meta = cavlc_device.FlatMeta(np.asarray(sflat), h // 16)
            want = cavlc_device.assemble_annexb(
                np.asarray(sflat), meta, headers=enc.headers())
            assert au_s == want, f"GOP IDR diverges, session {s}"
            ref_1.append(tuple(recon))
        p_step, p_rows = h264_p_batch_step(mesh_g, h, w, qp=qp,
                                           deblock=True)
        ref_s = tuple(ref_s)
        for p in range(1, gop_p + 1):
            hvp, hlp = cavlc_device.slice_header_slots(
                h // 16, w // 16, frame_num=p, qp_delta=0,
                slice_type=5, idr=False)
            ys_p = np.ascontiguousarray(np.roll(ys[:ns_g], 4 * p, axis=2))
            cbs_p = np.ascontiguousarray(
                np.roll(cbs[:ns_g], 2 * p, axis=2))
            crs_p = np.ascontiguousarray(
                np.roll(crs[:ns_g], 2 * p, axis=2))
            flat_p, *ref_s = p_step(ys_p, cbs_p, crs_p, *ref_s,
                                    np.asarray(hvp), np.asarray(hlp))
            ref_s = tuple(ref_s)
            for r in ref_s:
                _assert_spread(r, ns_g * nx_g, f"(n/2,2) P{p} reference")
            flat_p = np.asarray(flat_p)
            for s in range(ns_g):
                au_s = assemble_session_h264(
                    flat_p[s], p_rows, nal_type=syn.NAL_SLICE,
                    ref_idc=2)
                sflat, ny, ncb, ncr, mv, nnz, _lv = \
                    cavlc_p_device.encode_p_cavlc_frame(
                        jnp.asarray(ys_p[s]), jnp.asarray(cbs_p[s]),
                        jnp.asarray(crs_p[s]), *ref_1[s],
                        jnp.asarray(hvp), jnp.asarray(hlp), qp)
                ref_1[s] = h264_deblock.deblock_frame(
                    ny, ncb, ncr, qp, nnz_blk=nnz, mv=mv)
                meta = cavlc_device.FlatMeta(np.asarray(sflat), h // 16)
                want = cavlc_device.assemble_annexb(
                    np.asarray(sflat), meta, nal_type=syn.NAL_SLICE,
                    ref_idc=2)
                assert au_s == want, (
                    f"GOP P{p} session {s}: sharded (halo+deblock) AU "
                    "diverges from single-device")
        gop_info = (f"; GOP IDR+{gop_p}P byte-identical on a "
                    f"({ns_g}x{nx_g}) mesh (halo + sharded deblock)")

    peak_host_mb = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 1024.0
    dev_mb = None
    try:
        stats = devices[0].memory_stats()
        if stats:
            dev_mb = stats.get("peak_bytes_in_use", 0) / 1e6
    except Exception:
        pass
    print(f"dryrun ok (full-geometry h264): {n_devices} sessions at {w}x{h}, "
          f"AU bytes {sizes}, byte-identical to single-device; "
          f"peak host rss {peak_host_mb:.0f} MB"
          + (f", device peak {dev_mb:.0f} MB/chip" if dev_mb else "")
          + gop_info)


def dryrun(n_devices: int) -> None:
    """One tiny multi-session step over an n-device mesh (driver hook)."""
    devices = jax.devices()[:n_devices]
    ns = 2 if n_devices % 2 == 0 and n_devices > 1 else 1
    nx = n_devices // ns
    mesh = make_mesh((ns, nx), devices)

    s, h, w = ns * 2, 16 * nx * 2, 64
    frames = np.random.default_rng(0).integers(
        0, 255, size=(s, h, w, 3)).astype(np.uint8)

    tables = jpeg_device.uniform_dense_tables()
    step = batch_encode_step(mesh, h, w)
    packed, totals, hists = step(frames, *tables)
    packed, totals = np.asarray(packed), np.asarray(totals)
    assert packed.shape[0] == s and packed.shape[1] == nx
    assert (totals > 0).all()
    print(f"dryrun ok (mjpeg): mesh ({ns} session x {nx} spatial), "
          f"{s} sessions, {[int(t) for t in totals.sum(1)]} bits")

    # Flagship H.264 CAVLC over the same mesh (sessions x MB-row shards).
    rng = np.random.default_rng(1)
    ys = rng.integers(0, 255, size=(s, h, w)).astype(np.uint8)
    cbs = rng.integers(0, 255, size=(s, h // 2, w // 2)).astype(np.uint8)
    crs = rng.integers(0, 255, size=(s, h // 2, w // 2)).astype(np.uint8)
    h264_step, rows_local = h264_batch_encode_step(mesh, h, w, qp=30)
    flat = np.asarray(h264_step(ys, cbs, crs))
    assert flat.shape[:2] == (s, nx)
    aus = [assemble_session_h264(flat[i], rows_local) for i in range(s)]
    assert all(len(au) > 0 for au in aus)
    print(f"dryrun ok (h264): {s} sessions, "
          f"{[len(a) for a in aus]} AU bytes")

    # Context-parallel P step (halo exchange over the spatial axis) when
    # the geometry leaves enough chroma rows per shard.
    from ..ops import cavlc_device

    if p_halo_feasible(h, nx):
        from ..bitstream import h264 as syn

        hv, hl = cavlc_device.slice_header_slots(
            h // 16, w // 16, frame_num=1, slice_type=5, idr=False)
        p_step, p_rows = h264_p_batch_step(mesh, h, w, qp=30)
        ys2 = np.ascontiguousarray(np.roll(ys, 2, axis=2))
        pflat, nry, _, _ = p_step(ys2, cbs, crs, ys, cbs, crs,
                                  np.asarray(hv), np.asarray(hl))
        pflat = np.asarray(pflat)
        paus = [assemble_session_h264(pflat[i], p_rows,
                                      nal_type=syn.NAL_SLICE, ref_idc=2)
                for i in range(s)]
        assert all(len(a) > 0 for a in paus)
        print(f"dryrun ok (h264 P + halo exchange): "
              f"{[len(a) for a in paus]} AU bytes")

    # Real-geometry pass (BASELINE config 5), OPT-IN: it costs ~24 GB
    # peak host rss and minutes of CPU-XLA compile, so a pre-existing
    # quick smoke hook must not grow it by default.  Opt in with
    # GRAFT_DRYRUN_FULL=1 (the driver entry defaults it off too).
    import os

    if os.environ.get("GRAFT_DRYRUN_FULL", "0") == "1":
        dryrun_full_geometry(n_devices)
