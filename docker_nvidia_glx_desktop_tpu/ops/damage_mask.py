"""Damage-driven encode: per-frame device cost proportional to CHANGED
pixels, not frame area (ROADMAP item 3).

Real desktop traffic is overwhelmingly static.  The content plane
(ops/content_stats, PR 17) already measures per-MB frame-diff damage
in-graph; this module turns the SAME grid — same abs-SAD reduction, same
``DNGD_CONTENT_DAMAGE_THR`` threshold, computed host-side from the
ingest luma by :func:`damage_grid_np` — into a gating worklist, so
telemetry and gating cannot diverge (tests pin host-twin == device-grid
equality).

Why rows, not arbitrary MBs: the whole P pipeline is row-local by
construction — slice-per-MB-row entropy, deblocking_idc=2 (no filtering
across row seams), mvp=left-only, per-row mb_qp_delta chain resets, and
ME windows that never read more than ``_PAD`` pixels past the row band.
A damaged-ROW worklist therefore compacts cleanly: gather the damaged
rows' pixel bands, vmap the row-generic inter core over them, pack ONE
flat buffer whose meta describes exactly the damaged rows, and scatter
the recon rows back into the reference ring.  Undamaged rows cost the
device nothing; on the wire they become host-cached all-skip P slices
(first_mb + mb_skip_run covering the row), whose decoder reconstruction
is bit-exactly the reference rows (P_Skip predicts the zero MV when the
left/top neighbors are unavailable-or-zero, which an all-skip slice
guarantees, and bS=0 edges leave the loop filter inert).

The worklist is PADDED to a power-of-two row bucket (duplicating a real
damaged row) so steady-state serving re-enters a small fixed set of
compiled programs as the damage fraction wanders — shape-polymorphic
worklists would retrace every frame (tests pin compile-silence).  A
fully-damaged frame falls back to the ordinary full-frame program,
which the 100%-damage byte-identity test pins as bit-exact with the
compacted program.

At ``tune="off"`` the row step's qp is a traced scalar
(:func:`row_step`, built as the ``_dynqp`` twins of ops/cavlc_p_device
are): ONE compiled program a row bucket serves the whole rate ladder, so
a mask session under CBR compiles nothing while it serves once the
buckets are warm (``H264Encoder.warm_pulls``).  Each bucket's program
carries its bucket in its name (``jit_encode_p_rows_b8``), so a device
trace says how many rows every traced frame was handed.  The hq tiers
keep the static form (:func:`encode_p_rows`).  The flat buffer has one
length whatever the bucket, so the pull ladder's slices are the dense
frame's.

Under the CABAC stream (``ENCODER_ENTROPY=cabac`` with the binarizer on the
device; PR 43) the worklist goes through :func:`row_step_cabac`
(``jit_encode_p_rows_cabac_b<bucket>``): the same gather, the same stages
under ``jax.vmap``, the loop filter and the scatter, and NO entropy stage —
the band's vectors and levels go to ``ops/cabac_binarize.binarize_p`` as
they are (a row is a slice there too, so nothing crosses a row), the host
engine codes the planned rows' record streams, and the untouched rows leave
as all-skip CABAC slices (``bitstream/h264_cabac.py``:
``skip_row_payload``, ``encode_p_rows_from_binstream``).  Its record buffer
grows with the bucket, so every bucket has a pull ladder of its own.

Knobs (all warn-and-default, utils/env):

- ``DNGD_DAMAGE_MASK``        master gate for damage-driven encode
  (default off: byte-stream identical to the pre-mask encoder).
- ``DNGD_DAMAGE_COST_FLOOR``  conservative floor of the damage-scaled
  per-session cost charge (fleet/capacity), default 0.35: an idle
  session is never modeled cheaper than 35% of its full-frame cost, so
  a fleet packed on idle sessions keeps spike headroom.
- ``DNGD_CONTENT_DAMAGE_THR`` (obs/content) — shared with telemetry:
  ONE threshold, one substrate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..bitstream import h264 as syn
from ..bitstream.bitwriter import BitWriter
from ..utils.env import env_flag, env_float
from .h264_inter import _PAD, RING_DONATE

try:
    import cv2 as _cv2
except Exception:                          # the numpy form serves
    _cv2 = None

__all__ = [
    "enabled", "cost_floor", "damage_factor", "damage_grid_np",
    "plan_rows", "RowPlan", "bucket_ladder", "encode_p_rows",
    "row_step", "ROW_STEP_DYNQP_STATIC", "row_core", "MASKED_ENTROPY",
    "row_core_cabac", "row_step_cabac",
    "skip_slice_nal", "assemble_masked_au", "force_skip_rows",
    "scatter_levels_np",
]


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """Master gate (DNGD_DAMAGE_MASK). Default OFF: with the mask off
    the encoder's byte stream is identical to the pre-mask tree."""
    return env_flag("DNGD_DAMAGE_MASK", False)


def cost_floor() -> float:
    """Floor of the damage-scaled capacity charge, clamped to [0, 1]."""
    return min(max(env_float("DNGD_DAMAGE_COST_FLOOR", 0.35), 0.0), 1.0)


def damage_factor(damage, floor: float = None) -> float:
    """Charge factor for a session at rolling damage ``damage``:
    ``floor + (1 - floor) * damage``.  ``None`` damage (no telemetry
    yet) charges full cost — admission stays conservative until the
    content plane has evidence."""
    if damage is None:
        return 1.0
    f = cost_floor() if floor is None else min(max(floor, 0.0), 1.0)
    return f + (1.0 - f) * min(max(float(damage), 0.0), 1.0)


# ---------------------------------------------------------------------------
# the host twin of the device damage grid (ONE substrate)
# ---------------------------------------------------------------------------

def damage_grid_np(y: np.ndarray, prev_y, thr_sad: int = None) -> np.ndarray:
    """(R, C) uint8 damaged-MB grid of two uint8 lumas — the exact numpy
    twin of ``ops.content_stats._damage_grid`` (same per-MB abs-SAD sum, same
    threshold), evaluated host-side from the ingest luma so gating needs
    no device round-trip.  ``prev_y=None`` (stream start / resize)
    marks everything damaged.

    It runs on the session thread every P frame of a mask session, so it
    stays in the samples' own width: the absolute difference in uint8
    (cv2's where cv2 is there), sixteen rows summed in uint16 (at most
    16 x 255), the sixteen columns of those in uint32.  The same sums as
    the int64 form it replaced (tests/test_damage.py keeps that as the
    oracle), in a twentieth of the time at 2560x1600."""
    if thr_sad is None:
        from ..obs import content as obsc
        thr_sad = obsc.damage_thr_sad()
    r, c = y.shape[0] // 16, y.shape[1] // 16
    if prev_y is None:
        return np.ones((r, c), np.uint8)
    y, prev_y = np.asarray(y, np.uint8), np.asarray(prev_y, np.uint8)
    if _cv2 is not None:
        d = _cv2.absdiff(np.ascontiguousarray(y),
                         np.ascontiguousarray(prev_y))
    else:
        d = np.maximum(y, prev_y) - np.minimum(y, prev_y)
    cols = d.reshape(r, 16, c * 16).sum(axis=1, dtype=np.uint16)
    sad = cols.reshape(r, c, 16).sum(axis=2, dtype=np.uint32)
    return (sad > thr_sad).astype(np.uint8)


class RowPlan:
    """The host-side worklist for one frame: ``rows`` the damaged MB
    rows (sorted, unique), ``padded`` the bucket-padded int32 worklist
    the device program consumes (duplicates of the last damaged row —
    duplicate scatter writes are value-identical, so padding is free),
    ``bucket`` its length, ``full`` whether the plan covers every row
    (caller should use the ordinary full-frame program: bit-exact and
    cheaper than a frame-sized gather)."""

    __slots__ = ("rows", "padded", "bucket", "total", "frac")

    def __init__(self, rows, padded, bucket, total, frac):
        self.rows = rows
        self.padded = padded
        self.bucket = bucket
        self.total = total
        self.frac = frac

    @property
    def full(self) -> bool:
        return self.bucket >= self.total


def _bucket_for(n: int, total: int) -> int:
    """Smallest power-of-two >= n, capped at the frame's row count —
    the fixed compile ladder (1, 2, 4, ... total)."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, total)


def bucket_ladder(total: int) -> list:
    """Every bucket of a ``total``-row frame that the ROW program codes
    (1, 2, 4 ... below ``total``; ``total`` itself is the full-frame
    program's): what set-up compiles."""
    return sorted({_bucket_for(n, total) for n in range(1, total)}
                  - {total})


def plan_rows(grid: np.ndarray) -> RowPlan:
    """Damaged-row worklist from a damage grid.  A fully-calm frame
    still encodes ONE row (row 0) on device: every P frame is one
    dispatch and one pull, as on the unmasked encoder (a frame whose
    programs the device never ran would have no token to collect), and
    an undamaged row encodes to the same all-skip slice bytes the host
    cache would emit."""
    total = int(grid.shape[0])
    rows = np.flatnonzero(grid.any(axis=1)).astype(np.int32)
    frac = float(grid.mean()) if grid.size else 0.0
    if rows.size == 0:
        rows = np.zeros(1, np.int32)
    bucket = _bucket_for(int(rows.size), total)
    if bucket >= total:
        padded = np.arange(total, dtype=np.int32)
        return RowPlan(padded, padded, total, total, frac)
    padded = np.concatenate(
        [rows, np.full(bucket - rows.size, rows[-1], np.int32)])
    return RowPlan(rows, padded, bucket, total, frac)


# ---------------------------------------------------------------------------
# the compacted device program
# ---------------------------------------------------------------------------

def row_core(y, cb, cr, ref_y, ref_cb, ref_cr, rows, hv_r, hl_r,
             qp: int, tune: str = "off", next_y=None,
             p_intra: bool = False, deblock: bool = False):
    """Row-compacted P encode: the shared un-jitted core BOTH the
    per-frame step and the chunk-ring scan body run (one implementation,
    so the two paths' bytes cannot drift).

    ``rows`` (R_b,) int32 gathers the damaged rows; ``hv_r``/``hl_r``
    are those rows' slice-header slots (full-frame header slots indexed
    by the same worklist).  Returns the unmasked step's 7-tuple
    ``(flat, ref_y', ref_cb', ref_cr', mv, nnz, levels)`` with the flat
    meta describing R_b rows and the recon rows scattered back into the
    full reference planes — downstream (pull-prefix, ring chain,
    overflow fallback) is shape-compatible by construction.
    """
    from . import cavlc_p_device, h264_deblock

    out = _code_rows(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp, tune,
                     next_y, p_intra)
    flat, ry, rcb, rcr, mv, nnz, levels = cavlc_p_device._finish_p(
        out, hv_r, hl_r, slice_qp=qp)
    if deblock:
        # idc=2 keeps every MB row independent, so filtering the
        # compacted row stack equals filtering the full frame and
        # gathering — the same argument the spatial shards rest on
        ry, rcb, rcr = h264_deblock.deblock_frame.__wrapped__(
            ry, rcb, rcr, qp, nnz_blk=nnz, mv=mv.astype(jnp.int32))
    new_ry, new_rcb, new_rcr = _scatter_rows(
        ref_y, ref_cb, ref_cr, rows, ry, rcb, rcr)
    return flat, new_ry, new_rcb, new_rcr, mv, nnz, levels


def _code_rows(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp, tune: str,
               next_y, p_intra: bool) -> dict:
    """The way IN and the shared stages of both row programs (CAVLC's
    :func:`row_core`, CABAC's :func:`row_core_cabac`): the worklist's
    bands gathered out of the frame and the padded references, the
    row-generic inter core under ``jax.vmap``, its outputs merged into
    ONE frame of ``rows.shape[0]`` rows."""
    from . import h264_inter

    w = ref_y.shape[1]
    wc = w // 2
    rb = rows.shape[0]
    # dngd.mask_gather: the references' pad (the WHOLE planes, as int32:
    # the part of a frame's cost that does not shrink with the worklist)
    # and the bands cut out of them and out of the frame
    with jax.named_scope("dngd.mask_gather"):
        pry = jnp.pad(jnp.asarray(ref_y).astype(jnp.int32), _PAD,
                      mode="edge")
        prcb = jnp.pad(jnp.asarray(ref_cb).astype(jnp.int32), _PAD,
                       mode="edge")
        prcr = jnp.pad(jnp.asarray(ref_cr).astype(jnp.int32), _PAD,
                       mode="edge")

    def one(r):
        with jax.named_scope("dngd.mask_gather"):
            yb = jax.lax.dynamic_slice(y, (r * 16, 0), (16, w))
            cbb = jax.lax.dynamic_slice(cb, (r * 8, 0), (8, wc))
            crb = jax.lax.dynamic_slice(cr, (r * 8, 0), (8, wc))
            ryb = jax.lax.dynamic_slice(
                pry, (r * 16, 0), (16 + 2 * _PAD, w + 2 * _PAD))
            rcbb = jax.lax.dynamic_slice(
                prcb, (r * 8, 0), (8 + 2 * _PAD, wc + 2 * _PAD))
            rcrb = jax.lax.dynamic_slice(
                prcr, (r * 8, 0), (8 + 2 * _PAD, wc + 2 * _PAD))
            nyb = (None if next_y is None else
                   jax.lax.dynamic_slice(next_y, (r * 16, 0), (16, w)))
        return h264_inter.encode_p_frame_padded_ref(
            yb, cbb, crb, ryb, rcbb, rcrb, qp, tune=tune, next_y=nyb,
            p_intra=p_intra)

    # (vmap writes itself round the first scope inside it, "vmap(row)":
    # the stages' own names stay whole behind it, "vmap(row)/dngd.me_int",
    # which is how a trace's reduction finds them)
    outs = jax.vmap(jax.named_scope("row")(one))(rows)
    # per-row outputs carry a singleton row axis: (R_b, 1, C, ...) MB
    # tensors and (R_b, 16|8, W) planes — merge into one R_b-row frame
    # so the entropy stage packs ONE buffer across the worklist
    out = {}
    with jax.named_scope("dngd.mask_gather"):
        for k, v in outs.items():
            out[k] = v.reshape((rb * v.shape[1],) + v.shape[2:]) \
                if k.startswith("recon") else \
                v.reshape((rb,) + v.shape[2:])
    return out


def _scatter_rows(ref_y, ref_cb, ref_cr, rows, ry, rcb, rcr):
    """The way OUT of both row programs: the (possibly filtered) recon
    rows back into the ring; duplicate padded indices write identical
    values, so scatter order cannot matter."""
    h, w = ref_y.shape
    wc = w // 2
    rb = rows.shape[0]
    with jax.named_scope("dngd.mask_scatter"):
        new_ry = jnp.asarray(ref_y).reshape(h // 16, 16, w).at[rows].set(
            ry.reshape(rb, 16, w)).reshape(h, w)
        new_rcb = jnp.asarray(ref_cb).reshape(h // 16, 8, wc).at[rows].set(
            rcb.reshape(rb, 8, wc)).reshape(h // 2, wc)
        new_rcr = jnp.asarray(ref_cr).reshape(h // 16, 8, wc).at[rows].set(
            rcr.reshape(rb, 8, wc)).reshape(h // 2, wc)
    return new_ry, new_rcb, new_rcr


@functools.partial(jax.jit,
                   static_argnames=("qp", "tune", "p_intra", "deblock"),
                   donate_argnames=RING_DONATE)
def encode_p_rows(y, cb, cr, ref_y, ref_cb, ref_cr, rows, hv_r, hl_r,
                  qp: int, tune: str = "off", next_y=None,
                  p_intra: bool = False, deblock: bool = False):
    """Jitted per-frame masked P step — :func:`row_core` specialized per
    (row bucket, qp, tune, p_intra, deblock).  The reference planes are
    donated exactly like the unmasked step (the scattered recon has the
    refs' shape/dtype, so XLA aliases the ring in place)."""
    return row_core(y, cb, cr, ref_y, ref_cb, ref_cr, rows, hv_r, hl_r,
                    qp, tune=tune, next_y=next_y, p_intra=p_intra,
                    deblock=deblock)


#: What the served row step is specialized on: NOT on ``qp``.  (The
#: benchmark's mask cell reads this before it touches the chip, and runs
#: no program whose row step compiles a rung of the rate ladder at a time:
#: benchmark/layer_metrics/_mask.py.)
ROW_STEP_DYNQP_STATIC = ("tune", "p_intra", "deblock")


@functools.lru_cache(maxsize=None)
def row_step(bucket: int):
    """The served row step of one bucket: the qp-traced twin of
    :func:`encode_p_rows` (tune="off" only — see
    cavlc_device.encode_intra_cavlc_frame_yuv_dynqp), one compiled
    program whatever the rate controller asks for, jitted under the
    bucket's own name: ``jit_encode_p_rows_b<bucket>`` on the device
    trace (a frame's program there starts with ``jit_encode_``), so the
    rows a traced frame gathered are read off its program's name."""
    def step(y, cb, cr, ref_y, ref_cb, ref_cr, rows, hv_r, hl_r, qp,
             tune="off", next_y=None, p_intra=False, deblock=False):
        assert rows.shape[0] == bucket, (rows.shape, bucket)
        return row_core(y, cb, cr, ref_y, ref_cb, ref_cr, rows, hv_r,
                        hl_r, qp, tune=tune, next_y=next_y,
                        p_intra=p_intra, deblock=deblock)

    step.__name__ = step.__qualname__ = f"encode_p_rows_b{bucket}"
    return jax.jit(step, static_argnames=ROW_STEP_DYNQP_STATIC,
                   donate_argnames=RING_DONATE)


#: The entropy placements whose P frames the mask compacts
#: (``H264Encoder._damage_plan``): the device CAVLC path (:func:`row_step`)
#: and, since PR 43, the CABAC path with the binarizer on the device
#: (:func:`row_step_cabac`).  (The benchmark's masked CABAC cell reads
#: this before it touches the chip: a program that does not say, or does
#: not name ``cabac``, drops the mask there and serves dense.)
MASKED_ENTROPY = ("device", "cabac")


def row_core_cabac(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp,
                   deblock: bool = False):
    """The CABAC stream's row-compacted P encode (tune="off"): the
    stages :func:`row_core` shares (:func:`_code_rows`), the loop filter
    over the worklist from the bS inputs the dense CABAC path takes
    (``models/h264._cabac_bs_inputs``: the luma levels' coded-block flags
    and the vectors), and the scatter into the reference planes.  No
    ``slots`` and no ``pack``: the worklist's vectors and levels go to
    ``ops/cabac_binarize.binarize_p`` as a band of ``rows.shape[0]`` rows
    (a row is a slice, so the band's records ARE the dense frame's for
    those rows).  Returns ``(ref_y', ref_cb', ref_cr', mv, levels)``."""
    from . import h264_deblock
    from .h264_device import nnz_blocks_raster

    out = _code_rows(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp, "off",
                     None, False)
    ry, rcb, rcr, mv = (out["recon_y"], out["recon_cb"], out["recon_cr"],
                        out["mv"])
    if deblock:
        with jax.named_scope("dngd.deblock_bs"):
            nnz, mv32 = nnz_blocks_raster(out["luma"]), mv.astype(jnp.int32)
        ry, rcb, rcr = h264_deblock.deblock_frame.__wrapped__(
            ry, rcb, rcr, qp, nnz_blk=nnz, mv=mv32)
    refs = _scatter_rows(ref_y, ref_cb, ref_cr, rows, ry, rcb, rcr)
    levels = {k: out[k] for k in ("luma", "cb_dc", "cb_ac", "cr_dc",
                                  "cr_ac")}
    return (*refs, mv, levels)


@functools.lru_cache(maxsize=None)
def row_step_cabac(bucket: int):
    """The served CABAC row step of one bucket, ``qp`` traced: one
    compiled program a bucket whatever the rate controller asks for,
    ``jit_encode_p_rows_cabac_b<bucket>`` on the device trace (a frame
    is counted by it, and the rows a traced frame gathered are read off
    its name, as off :func:`row_step`'s)."""
    def step(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp, deblock=False):
        assert rows.shape[0] == bucket, (rows.shape, bucket)
        return row_core_cabac(y, cb, cr, ref_y, ref_cb, ref_cr, rows, qp,
                              deblock=deblock)

    step.__name__ = step.__qualname__ = f"encode_p_rows_cabac_b{bucket}"
    return jax.jit(step, static_argnames=("deblock",),
                   donate_argnames=RING_DONATE)


# ---------------------------------------------------------------------------
# host-cached all-skip slices for the untouched rows
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8192)
def skip_slice_nal(first_mb: int, nc_mb: int, frame_num: int,
                   qp_delta: int, deblocking_idc: int) -> bytes:
    """One all-skip P slice NAL covering ``nc_mb`` MBs from
    ``first_mb``: slice header + mb_skip_run(nc_mb) + trailing bits.
    The decoder's reconstruction of this slice is the reference rows
    bit-exactly (P_Skip's MV predictor is forced to zero when the
    same-slice neighbors are absent or zero, and bS=0 edges leave the
    idc=2 loop filter inert), which is precisely what the device-side
    recon scatter left in the ring.  Cached on (first_mb, nc_mb,
    frame_num&0xF, qp_delta, idc) — a 16-frame GOP's worth of rows."""
    bw = BitWriter()
    syn.slice_header(bw, first_mb=first_mb, slice_type=5,
                     frame_num=frame_num & 0xF, idr=False,
                     qp_delta=qp_delta, deblocking_idc=deblocking_idc)
    syn.write_ue(bw, nc_mb)                 # mb_skip_run: the whole row
    syn.rbsp_trailing_bits(bw)
    return syn.nal_unit(syn.NAL_SLICE, bw.getvalue(), ref_idc=2)


def assemble_masked_au(flat_host: np.ndarray, meta, rows, nr_total: int,
                       nc_mb: int, *, frame_num: int, qp_delta: int = 0,
                       deblocking_idc: int = 1,
                       headers: bytes = b"") -> bytes:
    """Annex-B access unit for a masked frame: device-encoded rows from
    the compacted flat buffer interleaved IN RASTER ORDER with
    host-cached all-skip slices for every untouched row.  ``rows`` is
    the unpadded worklist (:attr:`RowPlan.rows`); padded duplicates at
    the meta tail are simply never referenced."""
    from .cavlc_device import META_WORDS

    base = META_WORDS * 4
    # first occurrence wins: meta rows [0, len(rows)) are the unique
    # damaged rows in worklist order
    slot = {}
    for i, r in enumerate(np.asarray(rows).tolist()):
        slot.setdefault(int(r), i)
    chunks = [headers]
    for r in range(nr_total):
        i = slot.get(r)
        if i is None:
            chunks.append(skip_slice_nal(r * nc_mb, nc_mb, frame_num,
                                         qp_delta, deblocking_idc))
        else:
            off = base + 4 * int(meta.word_off[i])
            rbsp = bytes(flat_host[off:off + int(meta.row_bytes[i])])
            chunks.append(syn.nal_unit(syn.NAL_SLICE, rbsp, ref_idc=2))
    return b"".join(chunks)


# ---------------------------------------------------------------------------
# forced-skip row mask (spatial shards + tests)
# ---------------------------------------------------------------------------

def force_skip_rows(out: dict, keep, ref_y, ref_cb, ref_cr) -> dict:
    """Force every MB of the rows where ``keep`` is False to P_Skip
    BEFORE entropy: zero mv/levels, reference rows as recon, intra off.
    ``p_mb_header_slots`` then emits those rows as pure skip runs —
    byte-identical to the host-cached all-skip slices — while the rows
    stay IN the device program (same shapes, no compaction).  This is
    the masked path of the spatial mesh, where the worklist cannot
    compact without repartitioning the shard_map: the ME/DCT work still
    runs, the bitstream and recon are gated.  ``ref_*`` are the
    UNPADDED local reference planes (halo cropped)."""
    keep = jnp.asarray(keep, bool)
    kmb = keep[:, None]
    res = dict(out)
    res["mv"] = jnp.where(kmb[..., None], out["mv"], 0)
    res["luma"] = jnp.where(kmb[..., None, None], out["luma"], 0)
    for k in ("cb_dc", "cr_dc"):
        res[k] = jnp.where(kmb[..., None], out[k], 0)
    for k in ("cb_ac", "cr_ac"):
        res[k] = jnp.where(kmb[..., None, None], out[k], 0)
    if "mb_intra" in out:
        res["mb_intra"] = jnp.asarray(out["mb_intra"], bool) & kmb
        res["i16_dc"] = jnp.where(kmb[..., None], out["i16_dc"], 0)
        res["i16_ac"] = jnp.where(kmb[..., None, None], out["i16_ac"], 0)
    ky = jnp.repeat(keep, 16)[:, None]
    kc = jnp.repeat(keep, 8)[:, None]
    res["recon_y"] = jnp.where(ky, out["recon_y"], jnp.asarray(ref_y))
    res["recon_cb"] = jnp.where(kc, out["recon_cb"], jnp.asarray(ref_cb))
    res["recon_cr"] = jnp.where(kc, out["recon_cr"], jnp.asarray(ref_cr))
    return res


# ---------------------------------------------------------------------------
# overflow fallback: scatter compacted levels to full-frame shapes
# ---------------------------------------------------------------------------

def scatter_levels_np(levels: dict, mv: np.ndarray, rows,
                      nr_total: int) -> tuple:
    """Host-side scatter of a compacted frame's level tensors and mv
    into full-frame shapes (untouched rows zero = skip), for the rare
    flat-cap overflow path where the host entropy coder re-emits the
    whole frame from levels.  Duplicated padded rows overwrite with
    identical values."""
    rows = np.asarray(rows)
    full_lv = {}
    for k, v in levels.items():
        v = np.asarray(v)
        full = np.zeros((nr_total,) + v.shape[1:], v.dtype)
        full[rows] = v
        full_lv[k] = full
    mv = np.asarray(mv)
    full_mv = np.zeros((nr_total,) + mv.shape[1:], mv.dtype)
    full_mv[rows] = mv
    return full_lv, full_mv
