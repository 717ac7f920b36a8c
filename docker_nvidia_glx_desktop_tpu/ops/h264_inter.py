"""H.264 P-frame (inter) stage on device: motion estimation, motion
compensation, residual transform/quant, closed-loop reconstruction.

The reference's inter coding lives in NVENC silicon (reference README.md:19-21
envelope).  TPU-first design decisions:

- **Slice-per-MB-row** (same as the intra stage): the MB row above is in
  another slice, so motion-vector prediction never crosses rows.  Per spec
  §8.4.1.3 with neighbors B/C unavailable, mvp = left MB's MV, and per
  §8.4.1.1 P_Skip motion is always (0,0) — the whole MV prediction chain is
  a row-local scan the host entropy stage can compute from the MV field.
- **Quarter-pel motion vectors** in a ±``SEARCH_R`` window,
  coarse-to-fine: a step-2 grid (81 alternate-line shifted-SAD maps —
  dense VPU work), a ±1 full-SAD integer re-rank, half-pel refinement
  over the three normative 6-tap interpolated planes (§8.4.2.2.1 b/h/j,
  computed once per reference frame as whole-plane filters — the
  TPU-friendly formulation), then quarter-pel refinement built from
  rounded averages of window slices (§8.4.2.2.1 a..s — no further
  filtering needed).  The refinement is LOCAL to the coarse minimum (an
  odd position far from it is unreachable — the standard coarse-to-fine
  trade).  Chroma MC is the normative 1/8-pel bilinear (§8.4.2.2.2;
  quarter-luma pels are eighth-chroma pels).  MV output is in
  QUARTER-pel units — mvd's native coding unit; a zero-MV bias plus
  refinement margins keep static content on (0,0) and skippable.
- Luma residual: 16 independent 4x4 blocks per MB (LumaLevel4x4 — inter
  MBs have no DC Hadamard); chroma keeps the 2x2 DC split (spec structure
  for ALL mb types).  Quantization uses the inter rounding offset.

Output dict (int16 where pulled by the host entropy stage):
  ``mv``      (R, C, 2)      luma MVs (dy, dx) in QUARTER-pel units
  ``luma``    (R, C, 16, 16) zigzag 4x4 levels, luma4x4BlkIdx order
  ``cb_dc``/``cr_dc`` (R, C, 4), ``cb_ac``/``cr_ac`` (R, C, 4, 15)
  ``recon_y``/``recon_cb``/``recon_cr`` full planes (device-resident
  reference for the next frame)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import quant
from .dct import fdct4x4, hadamard2x2, idct4x4
from .h264_device import LUMA_BLOCK_ORDER, ZIGZAG4, _blocks, _unblocks

def ring_donate_argnames():
    """The reference-ring donation set for jitted P stages.

    Donation (aliasing the new recon into the old reference's buffer)
    is the ring's contract and what serving on
    TPU runs with.  On the CPU backend donated scan carries have shown
    latent heap corruption in jaxlib's CPU client (order-dependent
    malloc aborts bisected in round 8), so ``auto`` donates only when
    JAX_PLATFORMS names a non-cpu backend — never merely on the absence
    of ``cpu`` (with JAX_PLATFORMS unset jax falls back to the CPU on a
    TPU-less box, which must not re-enable the crash).  The deploy
    manifest and image set JAX_PLATFORMS=tpu, so a deployed pod donates
    and cannot start on the CPU by accident.  Resolved at
    import time from the environment so no jax backend is initialized
    early."""
    import os

    plats = os.environ.get("JAX_PLATFORMS", "")
    return (("ref_y", "ref_cb", "ref_cr")
            if plats and "cpu" not in plats else ())


#: resolved once; every ring-consuming jit in ops/ shares this set so
#: the donation story is one switch, not N
RING_DONATE = ring_donate_argnames()

SEARCH_R = 8          # +-8 luma pels integer search -> 17x17 candidates
ZERO_MV_BIAS = 128    # SAD bonus for (0,0): prefer skip-able MBs
HALF_BIAS = 96        # half-pel refine must beat integer by this margin
QUARTER_BIAS = 64     # quarter-pel refine margin over the half-pel best
_PAD = SEARCH_R + 5   # MV range + 6-tap reach + quarter-pel +1 neighbor

# tune=hq rate model for the lambda-scaled motion margins (bits): the
# mvd+cbp a zero-MV skip saves, and the extra mvd precision bits a
# half-/quarter-pel refinement costs.  Under tune=off the fixed SAD
# biases above apply unchanged (byte-identity contract).
_RATE_ZERO_BITS = 16.0
_RATE_HALF_BITS = 4.0
_RATE_QUARTER_BITS = 3.0
_RATE_SKIP_SIG_BITS = 12.0    # per-MB header bits a forced skip removes
_RATE_I16_HDR_BITS = 11.0     # I16-in-P header: mb_type ue + chroma + qpd


def _candidate_shifts():
    """Coarse stage: step-2 grid over the window (81 candidates); a +-1
    integer refinement recovers odd positions, so full coverage costs
    81+8 SAD maps instead of 289."""
    steps = np.arange(-SEARCH_R, SEARCH_R + 1, 2, dtype=np.int32)
    dy, dx = np.meshgrid(steps, steps, indexing="ij")
    return np.stack([dy.ravel(), dx.ravel()], axis=1)      # (81, 2)


@functools.lru_cache(maxsize=None)
def _pool_mat(m: int, n: int):
    """(m, m/n) block-pooling ones matrix (host-built, cached)."""
    return np.kron(np.eye(m // n, dtype=np.float32),
                   np.ones((n, 1), np.float32))


def _block_sum_mm(x, nh, nw):
    """(H, W) -> (H/nh, W/nw) sums as two ones-matrix matmuls on the MXU.

    The textbook reshape+reduce formulation costs a physical layout
    change per call — at 81 SAD maps per P frame the coarse ME loop spent
    ~12 ms/frame in those reshapes/reduces (profiled on v5e).  Pooling is
    a matmul with a block-diagonal ones matrix.  The first dot's operands
    (abs-diffs <= 255, 0/1 pool matrix) are bf16-exact with f32 MXU
    accumulation, so default precision is already exact on the large
    matmul; the SECOND dot consumes the first stage's sums ``y`` (up to
    16*255 = 4080, NOT bf16-representable), so the whole op needs
    HIGHEST — never a per-operand (HIGHEST, DEFAULT) split — or
    coarse-ME SADs (and near-tie MV picks) go nondeterministic.
    """
    h, w = x.shape
    rw = jnp.asarray(_pool_mat(w, nw))                  # (W, W/nw)
    rh = jnp.asarray(_pool_mat(h, nh))                  # (H, H/nh)
    y = jax.lax.dot_general(x.astype(jnp.float32), rw,
                            (((1,), (0,)), ((), ())))   # (H, W/nw)
    y = jax.lax.dot_general(rh, y, (((0,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST)
    return y.astype(jnp.int32)                          # (H/nh, W/nw)


def _tap6(x, axis):
    """Normative 6-tap half-pel filter (1, -5, 20, 20, -5, 1) along
    ``axis`` WITHOUT rounding/shift — returns the b1/h1 intermediates
    (spec §8.4.2.2.1).  Output is 5 samples shorter than the input; index
    i holds the half-sample between input i+2 and i+3."""
    def s(k):
        sl = [slice(None)] * x.ndim
        n = x.shape[axis] - 5
        sl[axis] = slice(k, k + n)
        return x[tuple(sl)]

    return s(0) - 5 * s(1) + 20 * s(2) + 20 * s(3) - 5 * s(4) + s(5)


def _halfpel_planes(ref_pad):
    """The three half-sample planes of an edge-padded reference.

    Returns (b, h, j) aligned so that index (y, x) of each plane is the
    half-sample at (y + frac/2, x + frac/2) of ``ref_pad[2:-3, 2:-3]`` —
    callers gather with a uniform +2 base offset into ref_pad coordinates.
    """
    b1 = _tap6(ref_pad, 1)                       # (H, W-5) horizontal
    b = jnp.clip((b1 + 16) >> 5, 0, 255)
    h1 = _tap6(ref_pad, 0)                       # (H-5, W) vertical
    h = jnp.clip((h1 + 16) >> 5, 0, 255)
    # center: vertical 6-tap over the b1 intermediates (non-rounded)
    j1 = _tap6(b1, 0)                            # (H-5, W-5)
    j = jnp.clip((j1 + 512) >> 10, 0, 255)
    return b[2:-3, :], h[:, 2:-3], j             # align all to (H-5, W-5)


# ---------------------------------------------------------------------------
# Gather-free per-MB displaced access
#
# ``plane[mb_base + per_mb_offset + (i, j)]`` is the core access pattern of
# motion compensation and local SAD refinement.  A general gather expresses
# it directly but runs at ~130M elements/s on TPU (measured on v5e) — the
# first version of this module spent ~500 ms/frame in exactly such gathers
# (17 full-frame gathers across the two refinement stages, the final MC,
# and chroma).  The structured replacement:
#
#   1. `_tiles` cuts the plane into per-MB *overlapping* spans via static
#      strided slices (XLA views, no data-dependent addressing);
#   2. `_mb_windows` selects each MB's displacement out of the bounded MV
#      range with a one-hot select-accumulate over the two axes (pure VPU
#      mads XLA fuses; the same trade as cavlc_device._onehot_lookup).
#
# Every candidate evaluation and the final prediction then become *static*
# slices of the per-MB window.
# ---------------------------------------------------------------------------


def _tiles(plane, base_y: int, base_x: int, tile: int, span: int,
           nr: int, nc: int):
    """Overlapping per-MB spans by static strided slicing.

    T[r, c, u, v] = plane[r*tile + base_y + u, c*tile + base_x + v]
    for u, v in [0, span).  ``plane`` must cover the addressed range.
    """
    rows = [plane[base_y + u: base_y + u + (nr - 1) * tile + 1: tile, :]
            for u in range(span)]
    a = jnp.stack(rows, axis=1)                       # (nr, span, Wp)
    cols = [a[:, :, base_x + v: base_x + v + (nc - 1) * tile + 1: tile]
            for v in range(span)]
    t = jnp.stack(cols, axis=3)                       # (nr, span, nc, span)
    return t.transpose(0, 2, 1, 3)                    # (nr, nc, span, span)


def _select_axis(arr, off, axis: int, span_off: int, width: int):
    """Narrow ``arr`` along ``axis`` to ``width`` starting at per-MB
    offset ``off`` in [0, span_off], by RADIX decomposition
    (off = 4a + b): the flat one-hot costs span_off+1 select-accumulate
    passes over the frame-sized buffer; two radix levels cost
    ceil((span_off+1)/4) + 4, about half the passes (and the level-2
    passes run on an already-narrowed buffer).  Exact repositioning —
    the masks per level are disjoint and complete."""
    dt = arr.dtype
    n_hi = span_off // 4 + 1
    hi = off // 4
    lo = off - hi * 4
    lo_max = min(3, span_off)
    w_mid = width + lo_max

    def take(a, axis, start, w):
        sl = [slice(None)] * a.ndim
        sl[axis] = slice(start, start + w)
        return a[tuple(sl)]

    # top-bucket mid slice may read past the span by up to lo_max; pad
    # with zeros — those rows are only selected for (hi=max, lo>0)
    # combinations that no valid offset produces
    overrun = 4 * (n_hi - 1) + w_mid - arr.shape[axis]
    if overrun > 0:
        padw = [(0, 0)] * arr.ndim
        padw[axis] = (0, overrun)
        arr = jnp.pad(arr, padw)

    shape_mask = off.shape + (1, 1)
    acc = jnp.zeros(arr.shape[:axis] + (w_mid,) + arr.shape[axis + 1:], dt)
    for a in range(n_hi):
        m = (hi == a).reshape(shape_mask)
        acc = acc + jnp.where(m, take(arr, axis, 4 * a, w_mid),
                              jnp.zeros((), dt))
    out = jnp.zeros(arr.shape[:axis] + (width,) + arr.shape[axis + 1:], dt)
    for b in range(lo_max + 1):
        m = (lo == b).reshape(shape_mask)
        out = out + jnp.where(m, take(acc, axis, b, width),
                              jnp.zeros((), dt))
    return out


def _mb_windows(tiles, off_y, off_x, dlim: int, size: int):
    """Per-MB ``size``-wide windows displaced by per-MB integer offsets.

    tiles: (R, C, span, span) with span = size + 2*dlim, aligned so that
    offset 0 starts at (dlim, dlim).  off_y/off_x: (R, C) in [-dlim, dlim].
    Returns (R, C, size, size) via radix select-accumulates per axis, in
    the tiles' dtype (pass uint8 sample planes: the per-MB masks are
    disjoint so narrow accumulation cannot overflow, and the narrow dtype
    cuts the dominant HBM traffic of these frame-sized buffers ~40%).
    """
    # bounds: the top hi-bucket's mid slice can read up to lo_max past
    # the span; _select_axis's zero-pad branch covers exactly that
    # overrun (those padded rows are unreachable for valid offsets) —
    # do NOT remove it as dead code
    acc = _select_axis(tiles, (off_y + dlim).astype(jnp.int32), 2,
                       2 * dlim, size)
    return _select_axis(acc, (off_x + dlim).astype(jnp.int32), 3,
                        2 * dlim, size)


@functools.partial(jax.jit,
                   static_argnames=("qp", "tune", "p_intra"),
                   donate_argnames=RING_DONATE)
def encode_p_frame(y, cb, cr, ref_y, ref_cb, ref_cr, qp: int,
                   tune: str = "off", next_y=None, p_intra: bool = False):
    """Device stage for one P frame (planes already MB-padded).

    The reference planes are DONATED (:data:`RING_DONATE`; empty only
    on the CPU fallback backend): recon_y/recon_cb/recon_cr have the
    exact shape/dtype of ref_y/ref_cb/ref_cr, so XLA writes the new
    reference into the old one's buffer — the ring-buffer step ROADMAP
    item 2 calls for, and the reason every caller must treat the passed
    refs as consumed (the encoder's ref chain hands each ref to exactly
    one P encode before replacing it; pass uint8 planes so the alias
    applies).  Nested use under an outer jit (devloop loops) traces
    through, where donation is inert by construction.

    ``tune``/``next_y``: the ENCODER_TUNE=hq axis — see
    :func:`encode_p_frame_padded_ref`."""
    with jax.named_scope("dngd.ingest"):
        # (the order of the parent's program: the persistent cache's key
        # leaves metadata out, and so still serves what it held)
        refs = [jnp.asarray(r).astype(jnp.int32)
                for r in (ref_y, ref_cb, ref_cr)]
        ref_pads = [jnp.pad(r, _PAD, mode="edge") for r in refs]
    return encode_p_frame_padded_ref(
        y, cb, cr, *ref_pads, qp, tune=tune, next_y=next_y,
        p_intra=p_intra)


#: qp-traced twin (tune="off" only), for the per-frame CABAC path — see
#: cavlc_device.encode_intra_cavlc_frame_yuv_dynqp.
encode_p_frame_dynqp = jax.jit(
    encode_p_frame.__wrapped__,
    static_argnames=("tune", "p_intra"),
    donate_argnames=RING_DONATE)


def encode_p_frame_padded_ref(y, cb, cr, ref_y_pad, ref_cb_pad, ref_cr_pad,
                              qp: int, tune: str = "off", next_y=None,
                              p_intra: bool = False):
    """Core P stage with the references ALREADY padded by ``_PAD`` on every
    side.  Single-device callers pad with edge replication; the
    spatially-sharded batch path supplies neighbor-shard rows instead (the
    halo exchange — SURVEY.md §5's context-parallel analog), which is the
    only difference between a sharded and a monolithic encode.

    The integer re-rank and both subpel-refinement stages score their
    SADs on every other luma line (half the residual-window work).  The
    final prediction is the exact normative interpolation at the winning
    MV, so the bitstream stays conformant — the alternate-line scale only
    moves WHICH conformant MV wins near ties.

    ``tune`` (ENCODER_TUNE): "off" keeps every decision and output
    byte-identical to the pre-tune encoder.  "hq" turns the fixed SAD
    margins (ZERO/HALF/QUARTER biases) into lambda(QP)-scaled rate
    costs, adds a Lagrangian forced-skip decision (a zero-MV MB whose
    coded residual buys less SSD than lambda times its bits is coded as
    P_Skip), and quantizes under a per-MB qp plane from luma activity
    (ops/aq) with an optional 1-frame lookahead bias from ``next_y``
    (the chunk ring's already-staged next frame).  "hq_noaq" keeps the
    lambda decisions but pins the qp plane flat (deblock-compatible).

    ``p_intra`` (tune=hq/hq_noaq only): let the Lagrangian mode decision
    code a P-slice MB as I_16x16 (DC prediction) where intra beats both
    the motion-compensated candidate and skip — the normative escape for
    content motion estimation cannot track (spec 7.4.5, P-slice mb_type
    >= 5).  Intra prediction in P slices reads the NEIGHBOR's final
    reconstruction, so the decision is run-parity gated along each row:
    an intra MB's left neighbor always stays inter, making the DC
    predictor this kernel computes (from the inter reconstruction)
    exactly what a conformant decoder derives.  Callers gate it off for
    entropy paths without I16-in-P plumbing (CABAC binarize, native C)
    and when the loop filter is on (intra bS rules are not modeled)."""
    with jax.named_scope("dngd.ingest"):
        y = jnp.asarray(y).astype(jnp.int32)
        cb = jnp.asarray(cb).astype(jnp.int32)
        cr = jnp.asarray(cr).astype(jnp.int32)
        ref_pad = jnp.asarray(ref_y_pad).astype(jnp.int32)
        ref_cb_pad = jnp.asarray(ref_cb_pad).astype(jnp.int32)
        ref_cr_pad = jnp.asarray(ref_cr_pad).astype(jnp.int32)
        if tune not in ("off", "hq", "hq_noaq"):
            raise ValueError(f"unknown tune {tune!r}")
        quant.require_static_qp_for(qp, tune)
        pad_h, pad_w = y.shape
        nr, nc = pad_h // 16, pad_w // 16

        qp_map = None
        if tune == "off":
            # qp may be a Python int (one program per qp) or a traced
            # scalar (one program for every qp — the served per-frame path)
            qp_q, qp_c = qp, quant.chroma_qp_any(qp)
            lam_d = lam_v = None
        else:
            from . import aq
            if tune == "hq":
                with jax.named_scope("dngd.aq"):
                    qp_map = aq.qp_plane(y, qp, next_y)     # (R, C)
                qp_q = qp_map
                qp_c = quant.chroma_qp_v(qp_map)
                lam_d = aq.lam_mode(qp_map)                 # (R, C) float32
                lam_v = aq.lam_mv(qp_map)
            else:
                qp_q, qp_c = qp, quant.chroma_qp(qp)
                lam_d = jnp.float32(aq.lam_mode(qp))
                lam_v = jnp.float32(aq.lam_mv(qp))

    with jax.named_scope("dngd.me_int"):
        # --- integer motion estimation: coarse grid ------------------------
        # Alternate-line SAD (even rows only): half the abs-diff traffic and
        # half the pooled rows for the map stage that evaluates 81 candidates
        # — the classic encoder trade.  The +-1/half/quarter refinement
        # stages below score on the SAME alternate-line scale (biases
        # halved with it).  The zero-MV bias here is halved to match the
        # half-sample magnitudes.
        shifts = jnp.asarray(_candidate_shifts())              # (81, 2)
        y_alt = y[0::2]

        def sad_for(shift):
            dy, dx = shift[0], shift[1]
            shifted = jax.lax.dynamic_slice(
                ref_pad, (_PAD + dy, _PAD + dx), (pad_h, pad_w))
            return _block_sum_mm(jnp.abs(y_alt - shifted[0::2]), 8, 16)

        sads = jax.lax.map(sad_for, shifts)                    # (81, R, C)
        zero_idx = shifts.shape[0] // 2                        # (0, 0) center
        # tune=hq replaces the fixed skip-ability bonus with a lambda-scaled
        # rate saving (~16 bits of mvd+cbp a zero-MV MB can skip), halved to
        # the alternate-line SAD scale of this stage
        if lam_v is None:
            zb_coarse = ZERO_MV_BIAS // 2
        else:
            zb_coarse = (lam_v * (_RATE_ZERO_BITS / 2)).astype(jnp.int32)
        sads = sads.at[zero_idx].add(-zb_coarse)
        best = jnp.argmin(sads, axis=0)                        # (R, C)
        mv_coarse = shifts[best]                               # (R, C, 2)

    with jax.named_scope("dngd.me_subpel"):
        # --- interpolated planes (shared cropped domain, +2 base) ----------
        b_pl, h_pl, j_pl = _halfpel_planes(ref_pad)
        full_pl = ref_pad[2:-3, 2:-3]

        cur_y = y.reshape(nr, 16, nc, 16).transpose(0, 2, 1, 3)

        neighbors = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
                     if (dy, dx) != (0, 0)]                    # static, 8
        neighbors_j = jnp.asarray(neighbors, dtype=jnp.int32)

        # Per-MB overlapping spans of the four planes (base_y=1 in plane
        # coords puts plane row r*16 + (_PAD-2) + t + i at span index
        # 10 + t + i; span 36 covers t in [-10, 10] — the mv_int range plus
        # the -1 of a half-pel floor AND the +1 right/below neighbor a
        # frac-3 quarter sample averages with).
        _SPAN = 36
        tiles4 = [_tiles(p.astype(jnp.uint8), 1, 1, 16, _SPAN, nr, nc)
                  for p in (full_pl, b_pl, h_pl, j_pl)]        # (R,C,36,36) x4

    with jax.named_scope("dngd.me_int"):
        # --- +-1 integer refinement of the coarse grid ---------------------
        # An 18-wide window aligned one pel above-left of mv_coarse holds all
        # nine candidates (center included) as static slices.  The re-rank
        # (and both subpel stages below) evaluates the residual window on
        # EVERY OTHER luma line — the same scale as the coarse stage, so
        # best_sad carries cleanly into the half-pel comparison and all
        # biases halve with it.  The (0,0) displacement keeps the zero-MV
        # bias — it is reachable only as the center of a zero coarse MV —
        # so static content stays skippable.
        srow = scale = 2
        cur_cmp = cur_y[:, :, 0::srow, :]

        w18 = _mb_windows(tiles4[0][:, :, 1:, 1:],
                          mv_coarse[..., 0], mv_coarse[..., 1], 8, 18)

        def w_sad(win, oy, ox, size=16):
            sl = win[:, :, 1 + oy: 1 + oy + size: srow,
                     1 + ox: 1 + ox + size]
            return jnp.abs(cur_cmp - sl.astype(jnp.int32)).sum(axis=(2, 3))

        cands = [(0, 0)] + neighbors
        int_sads = jnp.stack([w_sad(w18, oy, ox) for oy, ox in cands])
        is_zero = (mv_coarse[..., 0] == 0) & (mv_coarse[..., 1] == 0)
        if lam_v is None:
            zb_int = ZERO_MV_BIAS // scale
        else:
            zb_int = (lam_v * (_RATE_ZERO_BITS / scale)).astype(jnp.int32)
        int_sads = int_sads.at[0].add(jnp.where(is_zero, -zb_int, 0))
        best_int = jnp.argmin(int_sads, axis=0)                # (R, C)
        best_sad = jnp.take_along_axis(int_sads, best_int[None], axis=0)[0]
        mv_int = mv_coarse + jnp.asarray(cands, jnp.int32)[best_int]

    with jax.named_scope("dngd.me_subpel"):
        # --- half-pel refinement (normative 6-tap planes, §8.4.2.2.1) ------
        # 18-wide windows of all four planes aligned one pel above-left of
        # mv_int (one pel of margin each side: the low side serves half-pel
        # floors, the high side the +1 neighbors of frac-3 quarter samples):
        # neighbor (oy, ox) is plane parity (oy&1, ox&1) sliced at
        # (1 + (oy>>1), 1 + (ox>>1)) — floor semantics, matching mv>>1 of the
        # half-pel mv mv_int*2 + off.
        w17 = [_mb_windows(t, mv_int[..., 0], mv_int[..., 1], 9, 18)
               for t in tiles4]

        def wslice_s(p, ry, rx):
            """SAD view of plane p's window at integer offset (ry, rx)
            relative to mv_int — every ``srow``-th line."""
            return w17[p][:, :, 1 + ry: 17 + ry: srow, 1 + rx: 17 + rx]

        def half_slice_s(oy, ox):
            """SAD view of the half-pel candidate mv_int*2 + off."""
            p = (oy & 1) * 2 + (ox & 1)
            return wslice_s(p, oy >> 1, ox >> 1)

        half_sads = jnp.stack([
            jnp.abs(cur_cmp - half_slice_s(oy, ox).astype(jnp.int32)
                    ).sum(axis=(2, 3))
            for oy, ox in neighbors])                          # (8, R, C)
        best_half = jnp.argmin(half_sads, axis=0)              # (R, C)
        half_min = jnp.take_along_axis(
            half_sads, best_half[None], axis=0)[0]
        if lam_v is None:
            hb = HALF_BIAS // scale
        else:
            hb = (lam_v * (_RATE_HALF_BITS / scale)).astype(jnp.int32)
        use_half = half_min + hb < best_sad                    # (R, C)
        mv_h = mv_int * 2 + jnp.where(use_half[..., None],
                                      neighbors_j[best_half], 0)  # half-pel
        sad_h = jnp.where(use_half, half_min, best_sad)

        # --- quarter-pel refinement (spec §8.4.2.2.1 a..s) -----------------
        # Quarter samples are rounded averages of two full/half samples, so
        # every candidate is (A + B + 1) >> 1 of two static window slices.
        # The (plane, dy, dx) pairs per quarter fraction (fy, fx); the int
        # part and fraction of candidate mv_h*2+qoff depend on the SIGNED
        # half-pel offset hd = mv_h - 2*mv_int in {-1, 0, 1} per axis (parity
        # alone would alias off=-1 onto off=+1, displacing the window a full
        # pel), so each candidate one-hots over the nine (hy, hx) offsets —
        # e = 2*hd + qoff in [-3, 3] maps to rel = e>>2, frac = e&3.
        QPEL = {
            (0, 0): ((0, 0, 0),),
            (0, 1): ((0, 0, 0), (1, 0, 0)),       # a = (G + b + 1) >> 1
            (0, 2): ((1, 0, 0),),                 # b
            (0, 3): ((1, 0, 0), (0, 0, 1)),      # c = (b + H) — H right full
            (1, 0): ((0, 0, 0), (2, 0, 0)),       # d
            (1, 1): ((1, 0, 0), (2, 0, 0)),       # e = (b + h)
            (1, 2): ((1, 0, 0), (3, 0, 0)),       # f = (b + j)
            (1, 3): ((1, 0, 0), (2, 0, 1)),       # g = (b + m) — m right h
            (2, 0): ((2, 0, 0),),                 # h
            (2, 1): ((2, 0, 0), (3, 0, 0)),       # i = (h + j)
            (2, 2): ((3, 0, 0),),                 # j
            (2, 3): ((3, 0, 0), (2, 0, 1)),       # k = (j + m)
            (3, 0): ((2, 0, 0), (0, 1, 0)),      # n = (h + M) — M below full
            (3, 1): ((2, 0, 0), (1, 1, 0)),       # p = (h + s) — s below b
            (3, 2): ((3, 0, 0), (1, 1, 0)),       # q = (j + s)
            (3, 3): ((2, 0, 1), (1, 1, 0)),       # r = (m + s)
        }

        def qpred_s(ry, rx, fy, fx):
            """SAD view of the quarter-fraction prediction (every srow-th
            line) — rounded average of two static window slices."""
            parts = QPEL[(fy, fx)]
            p0, dy0, dx0 = parts[0]
            a = wslice_s(p0, ry + dy0, rx + dx0).astype(jnp.int32)
            if len(parts) == 1:
                return a
            p1, dy1, dx1 = parts[1]
            b = wslice_s(p1, ry + dy1, rx + dx1).astype(jnp.int32)
            return (a + b + 1) >> 1

        hdy = mv_h[..., 0] - 2 * mv_int[..., 0]                # (R, C) in
        hdx = mv_h[..., 1] - 2 * mv_int[..., 1]                # {-1, 0, 1}
        q_sads_l = []
        for qy, qx in neighbors:
            pk = jnp.zeros(cur_cmp.shape, jnp.int32)
            for hy in (-1, 0, 1):
                ey = 2 * hy + qy
                for hx in (-1, 0, 1):
                    ex = 2 * hx + qx
                    m = ((hdy == hy) & (hdx == hx))[..., None, None]
                    pk = pk + jnp.where(
                        m, qpred_s(ey >> 2, ex >> 2, ey & 3, ex & 3), 0)
            q_sads_l.append(jnp.abs(cur_cmp - pk).sum(axis=(2, 3)))
        q_sads = jnp.stack(q_sads_l)                           # (8, R, C)
        best_q = jnp.argmin(q_sads, axis=0)
        q_min = jnp.take_along_axis(q_sads, best_q[None], axis=0)[0]
        if lam_v is None:
            qb = QUARTER_BIAS // scale
        else:
            qb = (lam_v * (_RATE_QUARTER_BITS / scale)).astype(jnp.int32)
        use_q = q_min + qb < sad_h
        mv = mv_h * 2 + jnp.where(use_q[..., None],
                                  neighbors_j[best_q], 0)      # QUARTER units

    with jax.named_scope("dngd.mc"):
        # --- final luma MC: ONE full-height prediction at the chosen MV ----
        # The refinement stages above only ever build half-height SAD views;
        # the sole full-height prediction is assembled here.  Per axis
        # e = mv - 4*mv_int lies in [-3, 3]; rel = e>>2 (in {-1, 0}) and
        # frac = e&3 reproduce exactly the (window offset, fraction) mapping
        # the candidate evaluation used — so this is the same normative
        # §8.4.2.2.1 sample the winning candidate scored, for every
        # integer/half/quarter outcome.  Narrow the four 18-wide planes by
        # rel (two masked passes per axis), then one-hot over the 16 quarter
        # fractions.
        e_y = (mv[..., 0] - 4 * mv_int[..., 0])
        e_x = (mv[..., 1] - 4 * mv_int[..., 1])
        rel_y = (e_y >> 2)[..., None, None]
        rel_x = (e_x >> 2)[..., None, None]
        frac_y = (e_y & 3)[..., None, None]
        frac_x = (e_x & 3)[..., None, None]
        nw = []
        for t in w17:
            t = jnp.where(rel_y == -1, t[:, :, 0:17, :], t[:, :, 1:18, :])
            t = jnp.where(rel_x == -1, t[..., 0:17], t[..., 1:18])
            nw.append(t)                                       # (R, C, 17, 17)

        def qpred_full(fy, fx):
            parts = QPEL[(fy, fx)]
            p0, dy0, dx0 = parts[0]
            a = nw[p0][:, :, dy0: dy0 + 16, dx0: dx0 + 16].astype(jnp.int32)
            if len(parts) == 1:
                return a
            p1, dy1, dx1 = parts[1]
            b = nw[p1][:, :, dy1: dy1 + 16, dx1: dx1 + 16].astype(jnp.int32)
            return (a + b + 1) >> 1

        pred_y = jnp.zeros(cur_y.shape, jnp.int32)
        for fy in range(4):
            for fx in range(4):
                m = (frac_y == fy) & (frac_x == fx)
                pred_y = pred_y + jnp.where(m, qpred_full(fy, fx), 0)

        # --- chroma MC: 1/8-pel bilinear (spec §8.4.2.2.2) -----------------
        # quarter-luma pels ARE eighth-chroma pels: use mv directly
        c_off = mv >> 3                                        # in [-5, 4]
        c_frac = mv & 7

        def mc_chroma(rp):
            # 9-wide windows aligned at the chroma integer offset (mv is in
            # half-luma = quarter-chroma pels, so int_off = mv*2 >> 3 spans
            # [-5, 4]): span index int_off + 5 + i = plane row
            # r*8 + _PAD + int_off + i with base_y = _PAD - 5.
            t = _tiles(rp.astype(jnp.uint8), _PAD - 5, _PAD - 5, 8, 19, nr, nc)
            wc = _mb_windows(t, c_off[..., 0], c_off[..., 1], 5, 9)
            wc = wc.astype(jnp.int32)
            A = wc[:, :, :8, :8]
            B = wc[:, :, :8, 1:9]
            C = wc[:, :, 1:9, :8]
            D = wc[:, :, 1:9, 1:9]
            yf = c_frac[..., 0][..., None, None]
            xf = c_frac[..., 1][..., None, None]
            return ((8 - xf) * (8 - yf) * A + xf * (8 - yf) * B
                    + (8 - xf) * yf * C + xf * yf * D + 32) >> 6

        pred_cb = mc_chroma(ref_cb_pad)                        # (R, C, 8, 8)
        pred_cr = mc_chroma(ref_cr_pad)

        cur_cb = cb.reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3)
        cur_cr = cr.reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3)

    # --- luma residual: 16 x 4x4, no DC split --------------------------
    with jax.named_scope("dngd.mc"):
        res = _blocks(cur_y - pred_y, 4)                   # (R,C,4,4,4,4)
    with jax.named_scope("dngd.tq"):
        w = fdct4x4(res)
        lv = quant.h264_quantize_4x4(w, qp_q, intra=False)
    with jax.named_scope("dngd.recon"):
        wd = quant.h264_dequantize_4x4(lv, qp_q)
        recon_y_mb = jnp.clip(pred_y + _unblocks(idct4x4(wd)), 0, 255)

    with jax.named_scope("dngd.tq"):
        zz = jnp.asarray(ZIGZAG4)
        blk = jnp.asarray(LUMA_BLOCK_ORDER)
        luma_zz = lv.reshape(nr, nc, 4, 4, 16)[..., zz]    # (R,C,by,bx,16)
        luma_zz = luma_zz[:, :, blk[:, 1], blk[:, 0], :]   # blkIdx order

    # --- chroma residual: 2x2 DC Hadamard + AC -------------------------
    def chroma(cur, pred, qpc):
        with jax.named_scope("dngd.mc"):
            res = _blocks(cur - pred, 2)                   # (R,C,2,2,4,4)
        with jax.named_scope("dngd.tq"):
            w = fdct4x4(res)
            dc = w[..., 0, 0]                              # (R,C,2,2)
            ac = quant.h264_quantize_4x4(w, qpc, intra=False)
            ac = ac.at[..., 0, 0].set(0)
            dcl = quant.h264_quantize_chroma_dc(
                hadamard2x2(dc), qpc, intra=False)
        with jax.named_scope("dngd.recon"):
            fd = hadamard2x2(dcl)
            dcc = quant.h264_dequantize_chroma_dc(fd, qpc)
            wr = quant.h264_dequantize_4x4(ac, qpc)
            wr = wr.at[..., 0, 0].set(dcc)
            recon = jnp.clip(pred + _unblocks(idct4x4(wr)), 0, 255)
        with jax.named_scope("dngd.tq"):
            ac_zz = ac.reshape(
                ac.shape[:2] + (4, 16))[..., zz[1:]]       # (R,C,4,15)
            return ac_zz, dcl.reshape(dcl.shape[:2] + (4,)), recon

    cb_ac, cb_dc, recon_cb_mb = chroma(cur_cb, pred_cb, qp_c)
    cr_ac, cr_dc, recon_cr_mb = chroma(cur_cr, pred_cr, qp_c)

    with jax.named_scope("dngd.mode_decision"):
        if lam_d is not None:
            # --- Lagrangian forced-skip (tune=hq) --------------------------
            # A zero-MV MB whose coded residual buys less SSD than
            # lambda * its bits is coded as P_Skip: levels zeroed, the
            # reconstruction IS the prediction (what a decoder does for a
            # skipped MB), so the stream stays conformant by construction.
            from .h264_device import _level_bits_est

            zero_mv = jnp.all(mv == 0, axis=-1)                # (R, C)
            bits_mb = (_level_bits_est(lv, (2, 3, 4, 5))
                       + _level_bits_est(cb_ac, (2, 3))
                       + _level_bits_est(cb_dc, (2,))
                       + _level_bits_est(cr_ac, (2, 3))
                       + _level_bits_est(cr_dc, (2,))).astype(jnp.float32)

            def mb_ssd(a, b):
                d = a - b
                return (d * d).sum(axis=(2, 3)).astype(jnp.float32)

            d_coded = (mb_ssd(recon_y_mb, cur_y)
                       + mb_ssd(recon_cb_mb, cur_cb)
                       + mb_ssd(recon_cr_mb, cur_cr))
            d_skip = (mb_ssd(pred_y, cur_y) + mb_ssd(pred_cb, cur_cb)
                      + mb_ssd(pred_cr, cur_cr))
            force = zero_mv & (
                d_skip <= d_coded + lam_d * (bits_mb + _RATE_SKIP_SIG_BITS))
            f2 = force[:, :, None, None]
            luma_zz = jnp.where(f2, 0, luma_zz)
            cb_ac = jnp.where(f2, 0, cb_ac)
            cr_ac = jnp.where(f2, 0, cr_ac)
            cb_dc = jnp.where(force[:, :, None], 0, cb_dc)
            cr_dc = jnp.where(force[:, :, None], 0, cr_dc)
            recon_y_mb = jnp.where(f2, pred_y, recon_y_mb)
            recon_cb_mb = jnp.where(f2, pred_cb, recon_cb_mb)
            recon_cr_mb = jnp.where(f2, pred_cr, recon_cr_mb)

    is_intra = None
    with jax.named_scope("dngd.mode_decision"):
        if p_intra:
            # --- I_16x16-in-P Lagrangian mode decision (tune=hq) -----------
            # The intra escape for content ME cannot track (occlusions,
            # non-translational drift): code the MB I_16x16/DC where
            # SSD + lambda * bits beats BOTH the coded-inter and skip
            # candidates.  Intra prediction in a P slice reads the left
            # neighbor's final reconstruction (constrained_intra_pred_flag
            # is 0), so the decision is run-parity gated below: an intra
            # MB's left neighbor always stays inter, which makes the DC
            # predictor computed HERE (from the skip-merged inter recon)
            # exactly the sample set a conformant decoder derives.
            if lam_d is None:
                raise ValueError("p_intra requires tune=hq/hq_noaq")
            from .h264_device import _chroma_step, _i16_candidate

            n = nr * nc
            lam_f = jnp.broadcast_to(
                jnp.asarray(lam_d, jnp.float32), (nr, nc)).reshape(n)
            has_left = (jnp.arange(nc, dtype=jnp.int32) > 0)[None, :]
            has_left_f = jnp.broadcast_to(has_left, (nr, nc)).reshape(n)

            # luma candidate: DC from the left MB's reconstructed right col
            lcol_y = jnp.concatenate(
                [jnp.zeros((nr, 1, 16), jnp.int32),
                 recon_y_mb[:, :-1, :, 15]], axis=1).reshape(n, 16)
            ymb_f = cur_y.reshape(n, 16, 16)
            psum = (jnp.sum(lcol_y, axis=-1) + 8) >> 4
            pred_dc = jnp.where(has_left_f, psum, 128)[:, None, None]
            pred_dc = jnp.broadcast_to(pred_dc, ymb_f.shape)
            if qp_map is None:
                qp_i = qp
            else:
                qp_i = qp_map.reshape(n)
            ac_i, dc_i, rec_i, bits_y = _i16_candidate(ymb_f, pred_dc, qp_i)

            # chroma candidate: per-quadrant DC from the left chroma column
            qc_i = qp_c if qp_map is None else qp_c.reshape(n)
            lcol_cb = jnp.concatenate(
                [jnp.zeros((nr, 1, 8), jnp.int32),
                 recon_cb_mb[:, :-1, :, 7]], axis=1).reshape(n, 8)
            lcol_cr = jnp.concatenate(
                [jnp.zeros((nr, 1, 8), jnp.int32),
                 recon_cr_mb[:, :-1, :, 7]], axis=1).reshape(n, 8)
            hl3 = has_left_f[:, None, None]
            cbi_ac, cbi_dc, cbi_rec = _chroma_step(
                cur_cb.reshape(n, 8, 8), lcol_cb, hl3, qc_i)
            cri_ac, cri_dc, cri_rec = _chroma_step(
                cur_cr.reshape(n, 8, 8), lcol_cr, hl3, qc_i)

            from .h264_device import _level_bits_est as _lbe

            bits_i = (bits_y + _lbe(cbi_ac, (1, 2, 3, 4))
                      + _lbe(cbi_dc, (1, 2))
                      + _lbe(cri_ac, (1, 2, 3, 4))
                      + _lbe(cri_dc, (1, 2))).astype(jnp.float32)

            def flat_ssd(a, b):
                d = a.reshape(n, -1) - b.reshape(n, -1)
                return (d * d).sum(axis=1).astype(jnp.float32)

            d_intra = (flat_ssd(rec_i, ymb_f) + flat_ssd(cbi_rec, cur_cb)
                       + flat_ssd(cri_rec, cur_cr))
            score_intra = (d_intra
                           + lam_f * (bits_i + _RATE_I16_HDR_BITS))
            score_inter = jnp.where(
                force, d_skip + lam_d * 1.0,
                d_coded + lam_d * (bits_mb + _RATE_SKIP_SIG_BITS))
            want = score_intra.reshape(nr, nc) < score_inter       # (R, C)

            # run-parity gate: within each consecutive run of intra-wanting
            # MBs keep the even positions only, so no intra MB has an intra
            # left neighbor (whose recon the DC predictor above did not use)
            idx = jnp.arange(nc, dtype=jnp.int32)[None, :]
            last_not = jax.lax.cummax(jnp.where(~want, idx, -1), axis=1)
            is_intra = want & ((idx - last_not - 1) % 2 == 0)

            fI = is_intra[:, :, None, None]
            fI3 = is_intra[:, :, None]
            luma_zz = jnp.where(fI, 0, luma_zz)
            mv = jnp.where(fI3, 0, mv)
            cb_ac = jnp.where(fI, cbi_ac.reshape(n, 4, 16)[..., zz[1:]]
                              .reshape(nr, nc, 4, 15), cb_ac)
            cr_ac = jnp.where(fI, cri_ac.reshape(n, 4, 16)[..., zz[1:]]
                              .reshape(nr, nc, 4, 15), cr_ac)
            cb_dc = jnp.where(fI3, cbi_dc.reshape(nr, nc, 4), cb_dc)
            cr_dc = jnp.where(fI3, cri_dc.reshape(nr, nc, 4), cr_dc)
            recon_y_mb = jnp.where(fI, rec_i.reshape(nr, nc, 16, 16),
                                   recon_y_mb)
            recon_cb_mb = jnp.where(fI, cbi_rec.reshape(nr, nc, 8, 8),
                                    recon_cb_mb)
            recon_cr_mb = jnp.where(fI, cri_rec.reshape(nr, nc, 8, 8),
                                    recon_cr_mb)
            i16_dc_zz = dc_i.reshape(n, 16)[:, zz].reshape(nr, nc, 16)
            i16_ac_zz = ac_i.reshape(n, 4, 4, 16)[..., zz[1:]]
            i16_ac_zz = i16_ac_zz[:, blk[:, 1], blk[:, 0], :]      # blkIdx
            i16_ac_zz = i16_ac_zz.reshape(nr, nc, 16, 15)
            i16_dc_zz = jnp.where(fI3, i16_dc_zz, 0)
            i16_ac_zz = jnp.where(fI, i16_ac_zz, 0)

    def plane(mb, mbsz, ph, pw):
        return mb.transpose(0, 2, 1, 3).reshape(ph, pw)

    i16 = lambda a: a.astype(jnp.int16)
    with jax.named_scope("dngd.tq"):
        out = {
            "mv": mv.astype(jnp.int8),
            "luma": i16(luma_zz),
            "cb_dc": i16(cb_dc), "cb_ac": i16(cb_ac),
            "cr_dc": i16(cr_dc), "cr_ac": i16(cr_ac),
        }
    with jax.named_scope("dngd.recon"):
        ph2, pw2 = pad_h // 2, pad_w // 2
        out["recon_y"] = plane(recon_y_mb, 16, pad_h, pad_w).astype(jnp.uint8)
        out["recon_cb"] = plane(recon_cb_mb, 8, ph2, pw2).astype(jnp.uint8)
        out["recon_cr"] = plane(recon_cr_mb, 8, ph2, pw2).astype(jnp.uint8)
    if qp_map is not None:
        out["qp_map"] = qp_map        # (R, C) absolute per-MB qp (tune=hq)
    if is_intra is not None:
        out["mb_intra"] = is_intra            # (R, C) bool
        with jax.named_scope("dngd.tq"):
            out["i16_dc"] = i16(i16_dc_zz)    # (R, C, 16) zigzag
            out["i16_ac"] = i16(i16_ac_zz)    # (R, C, 16, 15) zigzag
    return out
