"""H.264 in-loop deblocking filter (spec 8.7) under slice-per-row.

The reference's NVENC applies the normative loop filter; rounds 1-2 of
this rebuild disabled it per slice header (legal, visibly blockier at
streaming QPs).  This module implements it TPU-first:

- **Slice structure does the parallelization**: with
  ``disable_deblocking_filter_idc=2`` the filter must not cross slice
  boundaries, and our slices ARE the MB rows — so only vertical edges
  (x=0,4,8,12 of each MB) and the INTERNAL horizontal edges (y=4,8,12)
  are filtered.  Every MB row is independent; the only sequencing is the
  spec's left-to-right MB order inside a row (MB n's x=0 edge reads and
  REWRITES the last columns of MB n-1 after n-1 finished): a chain of
  ``W/16`` MB columns, each filtered for all MB rows at once.
- **One arithmetic, two schedules, picked from the backend the code
  sees**: on a TPU the whole chain is ONE Pallas kernel
  (``dngd_deblock_edges``) with the MB rows on the lanes, so that an edge
  is elementwise work on eight (16, MB rows) tiles and no edge is an
  operation of its own; elsewhere it is a ``lax.scan`` over MB columns.
  At 1920x1088 on a v5e chip the program went from 5.0 ms a frame (a
  scan of 1,560 small edge filters on lane-sparse tiles) to 0.15 ms:
  0.065 the kernel, 0.06 XLA's turning of the planes round it, 0.02 the
  bS (PERF.md section 6, PR 26).
- **Filter tables** (Table 8-16/8-17 alpha/beta/tc0 — ~160 bytes of
  constants not derivable from formulas) are recovered STRUCTURALLY from
  the system libx264 .rodata, the same oracle pattern as the VP8
  probability tables (bitstream/vp8_tables.py): monotone 52-entry
  sequences with known heads/tails, cross-checked between two embedded
  copies.  Correctness is then pinned end-to-end: the conformant decoder
  (FFmpeg via cv2) applies ITS tables to our streams and must match our
  filtered reconstruction — wrong values desynchronize immediately and
  compound through every P frame.

The numpy reference (`deblock_frame_ref`) implements the spec order
literally; both device schedules are byte-identity-tested against it.
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = ["load_tables", "deblock_frame_ref"]

_LIBX264 = (
    "/lib/x86_64-linux-gnu/libx264.so.164",
    "/usr/lib/x86_64-linux-gnu/libx264.so.164",
)


def _candidate_paths():
    from ..utils.librecovery import candidate_paths
    return candidate_paths(fixed=_LIBX264, stems=("x264",))


@functools.lru_cache(maxsize=1)
def load_tables():
    """(alpha (52,), beta (52,), tc0 (52, 3)) int32, recovered + validated."""
    data = None
    for path in _candidate_paths():
        try:
            data = np.frombuffer(open(path, "rb").read(), np.uint8)
            break
        except OSError:
            continue
    if data is None:
        raise RuntimeError(
            "libx264 not found: deblock tables unavailable (install "
            "libx264 / ffmpeg; see deploy/Dockerfile)")
    raw = data.tobytes()

    # alpha: 52 entries, 16 leading zeros, nondecreasing, ends 255,255
    # with 226 before — a unique structural signature.
    alpha = None
    i = -1
    while True:
        i = raw.find(bytes([203, 226, 255, 255]), i + 1)
        if i < 0:
            break
        w = data[i + 4 - 52:i + 4].astype(np.int64)
        if (w[:16] == 0).all() and (np.diff(w) >= 0).all() and w[16] > 0:
            if alpha is not None and not (alpha == w).all():
                raise RuntimeError("ambiguous alpha recovery")
            alpha = w
    # beta: ends ...17,17,18,18 then x264's QP-extension padding of 18s;
    # anchor on the last strictly-increasing step (17,18) and require the
    # 36-entry nonzero tail plus 16 leading zeros.
    beta = None
    i = -1
    while True:
        i = raw.find(bytes([16, 17, 17, 18, 18, 18]), i + 1)
        if i < 0:
            break
        w = data[i + 5 - 52:i + 5].astype(np.int64)
        if (w[:16] == 0).all() and (np.diff(w) >= 0).all() and w[16] == 2:
            if beta is not None and not (beta == w).all():
                raise RuntimeError("ambiguous beta recovery")
            beta = w
    # tc0: stored as rows (255, bs1, bs2, bs3); the core's indexA=51 row
    # is the FIRST (255,13,17,25) (later copies are QP-extension padding).
    tc0 = None
    i = raw.find(bytes([255, 13, 17, 25]))
    if i >= 0:
        rows = data[i + 4 - 52 * 4:i + 4].reshape(52, 4).astype(np.int64)
        good = ((rows[:, 0] == 255).all()
                and (rows[0, 1:] == 0).all()
                and (np.diff(rows[:, 1:], axis=0) >= 0).all()
                and tuple(rows[51, 1:]) == (13, 17, 25))
        if good:
            tc0 = rows[:, 1:]
    if alpha is None or beta is None or tc0 is None:
        raise RuntimeError("deblock table recovery failed "
                           f"(alpha={alpha is not None} "
                           f"beta={beta is not None} tc0={tc0 is not None})")
    return (alpha.astype(np.int32), beta.astype(np.int32),
            tc0.astype(np.int32))


def _clip3(lo, hi, x):
    return np.minimum(hi, np.maximum(lo, x))


# ---------------------------------------------------------------------------
# Device implementation.  The spec's left-to-right order inside an MB row
# is a true dependency (MB n's x=0 edge rewrites MB n-1's last columns
# after n-1 finished), so the frame is filtered MB column by MB column,
# all MB rows at once.  One edge arithmetic (`_filter_lines`), two
# schedules: on the TPU one Pallas kernel runs the whole chain with the MB
# rows on the lanes; elsewhere a `lax.scan` over MB columns does.
# ---------------------------------------------------------------------------

def _filter_lines(p, q, bs, alpha, beta, tc0, chroma: bool):
    """Vectorized spec 8.7.2.3/8.7.2.4 over line bundles.

    p, q: four int32 arrays each, index 0 nearest the edge; bs: int32 of
    the same shape.  alpha/beta and the three tc0 entries are ints or
    int32 scalars.  Returns the samples that can change, nearest first:
    ((p0, p1, p2), (q0, q1, q2)) for luma, ((p0,), (q0,)) for chroma."""
    import jax.numpy as jnp

    p0, p1, p2, p3 = p
    q0, q1, q2, q3 = q
    fil = ((jnp.abs(p0 - q0) < alpha) & (jnp.abs(p1 - p0) < beta)
           & (jnp.abs(q1 - q0) < beta) & (bs > 0))
    bs4 = bs == 4
    sel = lambda strong, normal, old: jnp.where(
        fil, jnp.where(bs4, strong, normal), old)

    t0 = jnp.where(bs <= 1, tc0[0], jnp.where(bs == 2, tc0[1], tc0[2]))
    s_p0w = (2 * p1 + p0 + q1 + 2) >> 2
    s_q0w = (2 * q1 + q0 + p1 + 2) >> 2
    step = ((q0 - p0) * 4 + (p1 - q1) + 4) >> 3
    if chroma:
        delta = jnp.clip(step, -(t0 + 1), t0 + 1)
        return ((sel(s_p0w, jnp.clip(p0 + delta, 0, 255), p0),),
                (sel(s_q0w, jnp.clip(q0 - delta, 0, 255), q0),))

    ap = jnp.abs(p2 - p0) < beta
    aq = jnp.abs(q2 - q0) < beta

    # --- bS < 4 normal filter ---
    tc = t0 + ap.astype(jnp.int32) + aq.astype(jnp.int32)
    delta = jnp.clip(step, -tc, tc)
    n_p0 = jnp.clip(p0 + delta, 0, 255)
    n_q0 = jnp.clip(q0 - delta, 0, 255)
    avg = (p0 + q0 + 1) >> 1
    n_p1 = jnp.where(ap, p1 + jnp.clip((p2 + avg - 2 * p1) >> 1, -t0, t0),
                     p1)
    n_q1 = jnp.where(aq, q1 + jnp.clip((q2 + avg - 2 * q1) >> 1, -t0, t0),
                     q1)

    # --- bS == 4 strong filter ---
    strong = jnp.abs(p0 - q0) < ((alpha >> 2) + 2)
    use_p = strong & ap
    use_q = strong & aq
    s_p0 = jnp.where(use_p, (p2 + 2 * p1 + 2 * p0 + 2 * q0 + q1 + 4) >> 3,
                     s_p0w)
    s_p1 = jnp.where(use_p, (p2 + p1 + p0 + q0 + 2) >> 2, p1)
    s_p2 = jnp.where(use_p, (2 * p3 + 3 * p2 + p1 + p0 + q0 + 4) >> 3, p2)
    s_q0 = jnp.where(use_q, (q2 + 2 * q1 + 2 * q0 + 2 * p0 + p1 + 4) >> 3,
                     s_q0w)
    s_q1 = jnp.where(use_q, (q2 + q1 + q0 + p0 + 2) >> 2, q1)
    s_q2 = jnp.where(use_q, (2 * q3 + 3 * q2 + q1 + q0 + p0 + 4) >> 3, q2)

    return ((sel(s_p0, n_p0, p0), sel(s_p1, n_p1, p1), sel(s_p2, p2, p2)),
            (sel(s_q0, n_q0, q0), sel(s_q1, n_q1, q1), sel(s_q2, q2, q2)))


# --- the TPU schedule: one kernel, MB rows on the lanes -------------------
#
# A plane reaches the kernel MB column by MB column, each a 2-D tile whose
# row is ``x * 16 + line`` (x: pixel column inside the MB) and whose lane
# is the MB row: pixel column x is the 16 sublanes [16x, 16x + 16), line l
# the 16 sublanes l, l + 16, ... (a strided read).  Cb and Cr share their
# thresholds and their bS, so they are ONE plane here: 8 pixel columns of
# (Cb's 8 lines, Cr's 8 lines).  Every edge is then `_filter_lines` on
# eight (16, MB rows) tiles, luma and chroma alike.
#
# The x=0 edge of MB c+1 is filtered at the end of MB c's turn ("next
# edge"), so that a block of MB columns leaves the kernel final; its bS
# is stored with MB c.  Rows of the bS tile of one MB column:
_BS_NEXT, _BS_NEXT_C = 0, 16        # x=0 edge of the MB to the right
_BS_V, _BS_V_C = 32, 80             # luma x=4,8,12; chroma x=4
_BS_H, _BS_H_C = 96, 144            # luma y=4,8,12; chroma y=4
_BS_ROWS = 160
_COLS = 8                           # MB columns a grid step filters
# With a qp a macroblock the thresholds belong to an edge: a second tile of
# the bS tile's shape carries, line for line, the edge's thresholds as ONE
# word, alpha | beta << 8 | tc0[k] << 13 + 5k (8, 5 and three times 5
# bits), which the kernel reads as it reads bS.
_THR_BETA, _THR_TC0 = 8, (13, 18, 23)


def _thr_of_word(w):
    """(alpha, beta, tc0[3]) of threshold words, as `_filter_lines` takes
    them."""
    return (w & 0xFF, (w >> _THR_BETA) & 0x1F,
            tuple((w >> at) & 0x1F for at in _THR_TC0))


def _edges_kernel(thr, y_in, c_in, bs_in, *refs, per_edge: bool = False):
    """One block of MB columns: copy it and the first four pixel columns
    of the block to its right into the work tiles, run the chain, hand
    the (now half-filtered) columns of the right neighbour to the next
    grid step.  ``per_edge``: the thresholds come line by line from a tile
    behind the bS tile (``_thr_tiles``) and not from the ten scalars."""
    import jax
    from jax.experimental import pallas as pl

    thr_in = None
    if per_edge:
        thr_in, *refs = refs
    y_ahead, c_ahead, y_out, c_out, yw, cw, y_left, c_left = refs
    n = y_in.shape[0]
    lum = (thr[0], thr[1], (thr[2], thr[3], thr[4]), False)
    chrm = (thr[5], thr[6], (thr[7], thr[8], thr[9]), True)
    # work tile; pixel columns of an MB (and as many lines a plane);
    # samples a side that an edge can move; bS rows; thresholds
    planes = ((yw, 16, 3, _BS_V, _BS_H, _BS_NEXT, lum),
              (cw, 8, 1, _BS_V_C, _BS_H_C, _BS_NEXT_C, chrm))

    yw[:n] = y_in[...]
    cw[:n] = c_in[...]
    yw[n, :64] = y_ahead[0]
    cw[n, :64] = c_ahead[0]

    @pl.when(pl.program_id(1) > 0)     # not the first block of the chain
    def _():
        yw[0, :64] = y_left[...]
        cw[0, :64] = c_left[...]

    def column(g, _):
        bs = bs_in.at[g]

        def edge(p, q, at, par):
            """`_filter_lines` over the 16 lines whose bS is row ``at``."""
            if per_edge:
                par = (*_thr_of_word(thr_in.at[g][pl.ds(at, 16), :]),
                       par[3])
            return _filter_lines(p, q, bs[pl.ds(at, 16), :], *par)

        for w, ncols, keep, at_v, at_h, at_next, par in planes:
            mb, right = w.at[g], w.at[g + 1]
            # vertical edges inside the MB, on pixel-column tiles
            cols = [mb[pl.ds(x * 16, 16), :] for x in range(ncols)]
            for e, x in enumerate(range(4, ncols, 4)):
                pn, qn = edge([cols[x - 1 - k] for k in range(4)],
                              cols[x:x + 4], at_v + 16 * e, par)
                for k in range(keep):
                    cols[x - 1 - k], cols[x + k] = pn[k], qn[k]
            for x in range(4 - keep, ncols - 4 + keep):
                mb[pl.ds(x * 16, 16), :] = cols[x]
            # horizontal edges, on line tiles (every 16th row; chroma:
            # every 8th, Cb's and Cr's line side by side)
            lines = [mb[pl.ds(l, 16, stride=ncols), :]
                     for l in range(ncols)]
            for e, yy in enumerate(range(4, ncols, 4)):
                pn, qn = edge([lines[yy - 1 - k] for k in range(4)],
                              lines[yy:yy + 4], at_h + 16 * e, par)
                for k in range(keep):
                    lines[yy - 1 - k], lines[yy + k] = pn[k], qn[k]
            for l in range(4 - keep, ncols - 4 + keep):
                mb[pl.ds(l, 16, stride=ncols), :] = lines[l]
            # the MB edge to the right neighbour
            pn, qn = edge(
                [mb[pl.ds((ncols - 1 - k) * 16, 16), :] for k in range(4)],
                [right[pl.ds(k * 16, 16), :] for k in range(4)],
                at_next, par)
            for k in range(keep):
                mb[pl.ds((ncols - 1 - k) * 16, 16), :] = pn[k]
                right[pl.ds(k * 16, 16), :] = qn[k]

    jax.lax.fori_loop(0, n, column, None)
    y_left[...] = yw[n, :64]
    c_left[...] = cw[n, :64]
    y_out[...] = yw[:n]
    c_out[...] = cw[:n]


def _bs_tiles(nnz_blk, mv, nr: int, nc: int, mb_intra=None):
    """bS of every edge of a frame as the kernel reads it:
    (nc, _BS_ROWS, nr) int32 (see the row map above).  ``mb_intra``
    (R, C) bool: the I_16x16 macroblocks of a P picture (3 inside one, 4
    at a macroblock edge with one on either side: spec 8.7.2.1)."""
    import jax.numpy as jnp

    if nnz_blk is None or mb_intra is not None:
        col = jnp.arange(nc, dtype=jnp.int32)[:, None, None]
        row = jnp.arange(_BS_ROWS, dtype=jnp.int32)[None, :, None]
    if nnz_blk is None:                 # intra: 4 at MB edges, 3 inside
        bs = jnp.where(row < _BS_V, jnp.where(col < nc - 1, 4, 0), 3)
        return jnp.broadcast_to(bs, (nc, _BS_ROWS, nr))
    n = nnz_blk.astype(jnp.int32).transpose(1, 2, 3, 0)   # (C, by, bx, R)
    mvt = mv.transpose(1, 2, 0)                            # (C, 2, R)
    per4 = lambda a: jnp.repeat(a, 4, axis=1)   # a 4x4 block's 4 lines
    v_int = [per4((n[:, :, bx - 1] | n[:, :, bx]) * 2) for bx in (1, 2, 3)]
    h_int = [per4((n[:, by - 1] | n[:, by]) * 2) for by in (1, 2, 3)]
    mvd = (jnp.abs(mvt[1:] - mvt[:-1]) >= 4).any(axis=1)   # (C-1, R)
    nxt = jnp.where((n[:-1, :, 3] | n[1:, :, 0]) > 0, 2,
                    jnp.where(mvd[:, None], 1, 0))
    nxt = per4(jnp.pad(nxt, ((0, 1), (0, 0), (0, 0))))     # none at the end
    both = lambda a: jnp.concatenate([a[:, 0::2]] * 2, axis=1)  # (Cb, Cr)
    bs = jnp.concatenate(
        [nxt, both(nxt), *v_int, both(v_int[1]), *h_int,
         jnp.repeat(h_int[1][:, 0::2], 2, axis=1)], axis=1)
    if mb_intra is None:
        return bs
    it = jnp.asarray(mb_intra, bool).T[:, None, :]             # (C, 1, R)
    either = it | jnp.pad(it[1:], ((0, 1), (0, 0), (0, 0)))    # c or c + 1
    return jnp.where(row < _BS_V,
                     jnp.where(either & (col < nc - 1), 4, bs),
                     jnp.where(it, 3, bs))


def _lookup(table, q):
    """``table[q]`` for a table of 52 words and a plane of indices, as a
    select and a sum over the table's rows: elementwise work on 52 planes.
    As gathers the twenty lookups of a 1080p picture's four planes cost the
    chip 1.4 ms a frame, nine tenths of the filter's program (my chip run,
    PR 48)."""
    import jax.numpy as jnp

    t = jnp.asarray(table, jnp.int32).reshape((-1,) + (1,) * q.ndim)
    j = jnp.arange(t.shape[0], dtype=jnp.int32).reshape(t.shape)
    return jnp.sum(jnp.where(q[None] == j, t, 0), axis=0)


def _edge_thr_words(qp_eff):
    """The thresholds an edge is filtered by (spec 8.7.2.2: looked up by
    qPav), a word a macroblock, from the (R, C) plane of effective QPY:
    inside a macroblock by its own QP, at the edge to its RIGHT neighbour
    by the rounded mean of the two, for luma and, through each side's QPC,
    for chroma.  Returns (luma inside, luma right edge, chroma inside,
    chroma right edge), (R, C) each; the last column has no right edge
    (its bS is 0) and repeats itself."""
    import jax.numpy as jnp

    from . import quant as _q

    alpha_t, beta_t, tc0_t = load_tables()
    words = alpha_t | (beta_t << _THR_BETA)
    for k, at in enumerate(_THR_TC0):
        words = words | (tc0_t[:, k] << at)
    qy = jnp.clip(jnp.asarray(qp_eff, jnp.int32), 0, 51)
    qc = _lookup(_q.QPC_TABLE, qy)
    right = lambda a: jnp.concatenate([a[:, 1:], a[:, -1:]], axis=1)
    return tuple(_lookup(words, q) for q in (
        qy, (qy + right(qy) + 1) >> 1, qc, (qc + right(qc) + 1) >> 1))


def _thr_tiles(qp_eff):
    """The threshold word of every edge line, a tile of the bS tile's
    shape, (nc, _BS_ROWS, nr).  The tables are read a macroblock
    (`_edge_thr_words`), the lines only choose among what their macroblock
    looked up."""
    import jax.numpy as jnp

    row = jnp.arange(_BS_ROWS, dtype=jnp.int32)[None, :, None]
    at_next = row < _BS_V
    chroma = (((row >= _BS_NEXT_C) & (row < _BS_V))
              | ((row >= _BS_V_C) & (row < _BS_H)) | (row >= _BS_H_C))
    inside, nxt, c_inside, c_nxt = (
        w.T[:, None, :] for w in _edge_thr_words(qp_eff))      # (C, 1, R)
    return jnp.where(at_next, jnp.where(chroma, c_nxt, nxt),
                     jnp.where(chroma, c_inside, inside))


def _deblock_frame_kernel(y, cb, cr, lum, chrm, nnz_blk, mv, qp_eff=None,
                          mb_intra=None):
    """`deblock_frame` on the TPU: XLA lays planes and bS out (and back),
    one Pallas kernel filters every edge.  The thresholds reach it as ten
    scalars, so one kernel serves a static and a traced qp; with a qp a
    macroblock (``qp_eff``) they reach it as one more tile, a word a line
    of an edge."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, W = y.shape
    nr, nc = H // 16, W // 16
    nb = -(-nc // _COLS)                # blocks of MB columns: the chain
    nl = -(-nr // 128)                  # blocks of 128 MB rows: independent
    pad = lambda a: jnp.pad(
        a, ((0, nb * _COLS - nc), (0, 0), (0, nl * 128 - nr)))

    with jax.named_scope("dngd.deblock_bs"):
        thr = jnp.stack([jnp.asarray(v, jnp.int32) for v in
                         (*lum[:2], *lum[2], *chrm[:2], *chrm[2])])
        bs = pad(_bs_tiles(nnz_blk, mv, nr, nc, mb_intra))
        edge_thr = ()
        if qp_eff is not None:
            with jax.named_scope("dngd.deblock_thr"):
                edge_thr = (pad(_thr_tiles(qp_eff)),)
    with jax.named_scope("dngd.deblock_tile"):
        # (MB row, line, pixel column) -> (pixel column, line, MB row)
        turn = lambda p, n: (p.astype(jnp.int32).reshape(nr, n, -1)
                             .transpose(2, 1, 0))
        yt = pad(turn(y, 16).reshape(nc, 256, nr))
        ct = pad(jnp.concatenate([turn(cb, 8), turn(cr, 8)], axis=1)
                 .reshape(nc, 128, nr))

    # Mosaic reads whole 128-lane tiles, so a block is 128 MB rows wide;
    # the grid walks the column blocks of one row block, then the next's
    blk = lambda rows: pl.BlockSpec((_COLS, rows, 128),
                                    lambda r, i, thr: (i, 0, r))
    # the first four pixel columns of the MB column right of a block (of
    # the last MB column again behind the last block, where bS is 0)
    ahead = pl.BlockSpec(
        (1, 64, 128), lambda r, i, thr: (
            jnp.minimum((i + 1) * _COLS, nb * _COLS - 1), 0, r))
    with jax.named_scope("dngd.deblock_edges"):
        yt, ct = pl.pallas_call(
            (functools.partial(_edges_kernel, per_edge=True) if edge_thr
             else _edges_kernel),
            name="dngd_deblock_edges",
            out_shape=(jax.ShapeDtypeStruct(yt.shape, jnp.int32),
                       jax.ShapeDtypeStruct(ct.shape, jnp.int32)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(nl, nb),
                in_specs=[blk(256), blk(128), blk(_BS_ROWS),
                          *[blk(_BS_ROWS) for _ in edge_thr], ahead, ahead],
                out_specs=(blk(256), blk(128)),
                scratch_shapes=[
                    pltpu.VMEM((_COLS + 1, 256, 128), jnp.int32),
                    pltpu.VMEM((_COLS + 1, 128, 128), jnp.int32),
                    pltpu.VMEM((64, 128), jnp.int32),
                    pltpu.VMEM((64, 128), jnp.int32)]),
        )(thr, yt, ct, bs, *edge_thr, yt, ct)

    with jax.named_scope("dngd.deblock_tile"):
        back = lambda t: (t.astype(jnp.uint8).transpose(2, 1, 0)
                          .reshape(-1, t.shape[0]))
        ct = ct[:nc, :, :nr].reshape(W // 2, 16, nr)
        return (back(yt[:nc, :, :nr].reshape(W, 16, nr)),
                back(ct[:, :8]), back(ct[:, 8:]))


# --- the scan: the same chain where there is no TPU -----------------------

def _edge_v_mb(mb, x, bs, alpha, beta, tc0, chroma):
    """Filter the vertical edge at column ``x`` of (..., n, W) in place."""
    pn, qn = _filter_lines([mb[..., x - 1 - k] for k in range(4)],
                           [mb[..., x + k] for k in range(4)],
                           bs, alpha, beta, tc0, chroma)
    for k in range(len(pn)):
        mb = mb.at[..., x - 1 - k].set(pn[k])
        mb = mb.at[..., x + k].set(qn[k])
    return mb


def _edge_h_mb(mb, y, bs, alpha, beta, tc0, chroma):
    """Filter the horizontal edge at row ``y`` of (..., H, W) in place."""
    pn, qn = _filter_lines([mb[..., y - 1 - k, :] for k in range(4)],
                           [mb[..., y + k, :] for k in range(4)],
                           bs, alpha, beta, tc0, chroma)
    for k in range(len(pn)):
        mb = mb.at[..., y - 1 - k, :].set(pn[k])
        mb = mb.at[..., y + k, :].set(qn[k])
    return mb


import jax as _jax


@functools.partial(_jax.jit, static_argnames=("qp",))
def deblock_frame(y, cb, cr, qp: int, nnz_blk=None, mv=None, qp_eff=None,
                  mb_intra=None):
    """Device loop filter for one frame (slice-per-row, idc=2 edges).

    y (H, W), cb/cr (H/2, W/2) uint8 recon planes.  Intra frames pass
    nnz_blk=None (static bS: 4 at MB edges, 3 internal); P frames pass
    nnz_blk (R, C, 4, 4) bool and mv (R, C, 2) quarter-pel.  ``qp`` is
    static here and traced in :data:`deblock_frame_dynqp`.  Returns
    filtered uint8 planes, byte-identical to :func:`deblock_frame_ref`
    on either schedule (tested).

    A picture with a qp a macroblock (ENCODER_TUNE=hq) passes ``qp_eff``,
    the (R, C) plane of EFFECTIVE QPY (``aq.qp_chain``: a macroblock whose
    syntax carries no mb_qp_delta, a skipped one included, holds the one
    before it; the plane asked for is not what a decoder filters by), and
    a P picture with I_16x16 macroblocks their (R, C) flags ``mb_intra``.
    The thresholds then belong to an edge (`_edge_thr_words`) and the bS knows
    the intra rules; ``qp`` is not read.  With neither the program is the
    one it was (a branch at trace time)."""
    import jax
    import jax.numpy as jnp

    from . import quant as _q

    with jax.named_scope("dngd.deblock_bs"):
        alpha_t, beta_t, tc0_t = load_tables()
        if _q._is_static_qp(qp):
            qp_c = _q.chroma_qp(qp)
            a_l, b_l, t_l = (int(alpha_t[qp]), int(beta_t[qp]),
                             [int(v) for v in tc0_t[qp]])
            a_c, b_c, t_c = (int(alpha_t[qp_c]), int(beta_t[qp_c]),
                             [int(v) for v in tc0_t[qp_c]])
        else:
            # traced slice qp (deblock_frame_dynqp): the thresholds are
            # table gathers instead of folded constants — same integers
            qp_c = _q.chroma_qp_v(qp)
            alpha_a, beta_a = jnp.asarray(alpha_t), jnp.asarray(beta_t)
            tc0_a = jnp.asarray(tc0_t)
            a_l, b_l, t_l = alpha_a[qp], beta_a[qp], tc0_a[qp]
            a_c, b_c, t_c = alpha_a[qp_c], beta_a[qp_c], tc0_a[qp_c]
    lum, chrm = (a_l, b_l, t_l), (a_c, b_c, t_c)
    if _jax.default_backend() == "tpu":
        return _deblock_frame_kernel(y, cb, cr, lum, chrm, nnz_blk, mv,
                                     qp_eff, mb_intra)
    return _deblock_frame_scan(y, cb, cr, lum, chrm, nnz_blk, mv, qp_eff,
                               mb_intra)


def _deblock_frame_scan(y, cb, cr, lum, chrm, nnz_blk, mv, qp_eff=None,
                        mb_intra=None):
    """`deblock_frame` as a `lax.scan` over MB columns, one column a step
    (the CPU backend measured wider steps slower); ``lum`` / ``chrm`` are
    (alpha, beta, tc0[3]), and with ``qp_eff`` every column brings its own
    four of them: of the edge to its left neighbour and of its inside,
    luma and chroma, a row of the picture each."""
    import jax
    import jax.numpy as jnp

    H, W = y.shape
    nr, nc = H // 16, W // 16
    intra = nnz_blk is None

    with jax.named_scope("dngd.deblock_bs"):
        if not intra:
            nnz16y = jnp.repeat(nnz_blk.astype(jnp.int32), 4, axis=2)
            # (R, C, 16 lines, 4 bx) — per-line nnz along vertical edges
            bs_v_int = jnp.stack(
                [(nnz16y[:, :, :, bx - 1] | nnz16y[:, :, :, bx]) * 2
                 for bx in (1, 2, 3)], axis=2)                 # (R, C, 3, 16)
            left_nnz = jnp.concatenate(
                [jnp.zeros((nr, 1, 16), jnp.int32), nnz16y[:, :-1, :, 3]],
                axis=1)
            mvd = jnp.concatenate(
                [jnp.zeros((nr, 1), bool),
                 (jnp.abs(mv[:, 1:] - mv[:, :-1]) >= 4).any(-1)], axis=1)
            bs_mb0 = jnp.where((left_nnz | nnz16y[:, :, :, 0]) > 0, 2,
                               jnp.where(mvd[:, :, None], 1, 0))
            nnz16x = jnp.repeat(nnz_blk.astype(jnp.int32), 4, axis=3)
            bs_h_int = jnp.stack(
                [(nnz16x[:, :, by - 1] | nnz16x[:, :, by]) * 2
                 for by in (1, 2, 3)], axis=2)                 # (R, C, 3, 16)
            if mb_intra is not None:    # 3 inside I_16x16, 4 at its edges
                it = jnp.asarray(mb_intra, bool)
                either = it | jnp.pad(it[:, :-1], ((0, 0), (1, 0)))
                bs_mb0 = jnp.where(either[:, :, None], 4, bs_mb0)
                bs_v_int = jnp.where(it[:, :, None, None], 3, bs_v_int)
                bs_h_int = jnp.where(it[:, :, None, None], 3, bs_h_int)
            bs_mb0 = bs_mb0.at[:, 0].set(0)
            # scan-major layouts (C leading)
            bs_v_int = jnp.moveaxis(bs_v_int, 1, 0)            # (C, R, 3, 16)
            bs_mb0 = jnp.moveaxis(bs_mb0, 1, 0)                # (C, R, 16)
            bs_h_int = jnp.moveaxis(bs_h_int, 1, 0)

    with jax.named_scope("dngd.deblock_tile"):
        # MB-tiled planes, scan axis (MB column) leading
        ymbs = jnp.moveaxis(
            y.astype(jnp.int32).reshape(nr, 16, nc, 16).transpose(0, 2, 1, 3),
            1, 0)                                              # (C, R, 16, 16)
        cbm = jnp.moveaxis(
            cb.astype(jnp.int32).reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3),
            1, 0)
        crm = jnp.moveaxis(
            cr.astype(jnp.int32).reshape(nr, 8, nc, 8).transpose(0, 2, 1, 3),
            1, 0)

    thr_xs = ()
    if qp_eff is not None:
        with jax.named_scope("dngd.deblock_thr"):
            # a column's LEFT edge is its left neighbour's right edge
            left = lambda a: jnp.pad(a[:, :-1], ((0, 0), (1, 0)))
            li, ln, ci, cn = _edge_thr_words(qp_eff)
            thr_xs = tuple(w.T for w in (left(ln), li, left(cn), ci))  # (C, R)

    def col_step(carry, xs):
        yl, cbl, crl = carry            # left MB last-4 columns, post-H
        lum_l = lum_i = lum
        chrm_l = chrm_i = chrm
        if thr_xs:
            *xs, t_ll, t_li, t_cl, t_ci = xs
            lum_l, lum_i, chrm_l, chrm_i = (
                _thr_of_word(w[:, None]) for w in (t_ll, t_li, t_cl, t_ci))
        if intra:
            ymb, cbmb, crmb, idx = xs
            bs0 = jnp.full((nr, 16), 4, jnp.int32)
            bsv = [jnp.full((nr, 16), 3, jnp.int32)] * 3
            bsh = [jnp.full((nr, 16), 3, jnp.int32)] * 3
        else:
            ymb, cbmb, crmb, bsv3, bs0, bsh3, idx = xs
            bsv = [bsv3[:, e] for e in range(3)]
            bsh = [bsh3[:, e] for e in range(3)]
        has_left = idx > 0
        bs0 = jnp.where(has_left, bs0, 0)

        # --- luma: x=0 MB edge spans the carry (p) and this MB (q);
        # the H pass covers only THIS MB's 16 columns (the carry's H
        # edges were filtered in the previous step) ---
        with jax.named_scope("dngd.deblock_v"):
            wide = jnp.concatenate([yl, ymb], axis=-1)     # (R, 16, 20)
            wide = _edge_v_mb(wide, 4, bs0, *lum_l, False)
            for e, x in enumerate((4, 8, 12)):
                wide = _edge_v_mb(wide, 4 + x, bsv[e], *lum_i, False)
            left_fin = wide[..., :4]    # left MB cols 12..15, FINAL
            own = wide[..., 4:]
        with jax.named_scope("dngd.deblock_h"):
            for e, yy_ in enumerate((4, 8, 12)):
                own = _edge_h_mb(own, yy_, bsh[e], *lum_i, False)

        # --- chroma: MB edge + internal x=4 (luma x=8), h y=4 (luma 8) --
        def chroma_mb(mbp, left):
            with jax.named_scope("dngd.deblock_v"):
                w2 = jnp.concatenate([left, mbp], axis=-1)  # (R, 8, 12)
                w2 = _edge_v_mb(w2, 4, bs0[:, 0::2], *chrm_l, True)
                w2 = _edge_v_mb(w2, 8, bsv[1][:, 0::2], *chrm_i, True)
                lf, ownp = w2[..., :4], w2[..., 4:]
            with jax.named_scope("dngd.deblock_h"):
                ownp = _edge_h_mb(ownp, 4, bsh[1][:, 0::2], *chrm_i, True)
            return lf, ownp

        cbl_fin, cb_own = chroma_mb(cbmb, cbl)
        crl_fin, cr_own = chroma_mb(crmb, crl)

        carry = (own[..., -4:], cb_own[..., -4:], cr_own[..., -4:])
        out = (left_fin[..., 1:], own[..., :13],
               cbl_fin[..., 2:], cb_own[..., :6],
               crl_fin[..., 2:], cr_own[..., :6])
        return carry, out

    # the scan is one ``while`` on the device; the edges it filters carry
    # the two scopes inside it
    with jax.named_scope("dngd.deblock_edges"):
        init = (jnp.zeros((nr, 16, 4), jnp.int32),
                jnp.zeros((nr, 8, 4), jnp.int32),
                jnp.zeros((nr, 8, 4), jnp.int32))
        if intra:
            xs = (ymbs, cbm, crm, jnp.arange(nc, dtype=jnp.int32))
        else:
            xs = (ymbs, cbm, crm, bs_v_int, bs_mb0, bs_h_int,
                  jnp.arange(nc, dtype=jnp.int32))
        carry, outs = jax.lax.scan(col_step, init, xs + thr_xs)
        lf3, own13, cblf, cbo6, crlf, cro6 = outs

    def assemble(own_first, later_last, tailc, sub):
        """MB c's leading columns from step c, trailing columns from
        step c+1 (which finalized them via its x=0 edge)."""
        last = jnp.concatenate([later_last[1:], tailc[None]], axis=0)
        mbs = jnp.concatenate([own_first, last], axis=-1)   # (C,R,s,s)
        full = jnp.moveaxis(mbs, 0, 1)                      # (R,C,s,s)
        return full.transpose(0, 2, 1, 3).reshape(H // sub, W // sub)

    with jax.named_scope("dngd.deblock_tile"):
        y_out = assemble(own13, lf3, carry[0][..., 1:], 1)
        cb_out = assemble(cbo6, cblf, carry[1][..., 2:], 2)
        cr_out = assemble(cro6, crlf, carry[2][..., 2:], 2)
        clip = lambda p: jnp.clip(p, 0, 255).astype(jnp.uint8)
        return clip(y_out), clip(cb_out), clip(cr_out)


#: qp-traced twin: one program for every slice qp (see
#: cavlc_device.encode_intra_cavlc_frame_yuv_dynqp).
deblock_frame_dynqp = _jax.jit(deblock_frame.__wrapped__)


def _filter_line(p, q, bs, alpha, beta, tc0_row, chroma):
    """Filter ONE edge line (spec 8.7.2.3/8.7.2.4), in place on numpy
    int32 vectors p[0..3] (p0 nearest the edge) and q[0..3]."""
    if bs == 0:
        return
    p0, p1, p2, p3 = p[0], p[1], p[2], p[3]
    q0, q1, q2, q3 = q[0], q[1], q[2], q[3]
    if not (abs(int(p0 - q0)) < alpha and abs(int(p1 - p0)) < beta
            and abs(int(q1 - q0)) < beta):
        return
    if bs < 4:
        tc0 = int(tc0_row[bs - 1])
        ap = abs(int(p2 - p0)) < beta
        aq = abs(int(q2 - q0)) < beta
        if chroma:
            tc = tc0 + 1
        else:
            tc = tc0 + int(ap) + int(aq)
        delta = _clip3(-tc, tc, ((q0 - p0) * 4 + (p1 - q1) + 4) >> 3)
        p[0] = _clip3(0, 255, p0 + delta)
        q[0] = _clip3(0, 255, q0 - delta)
        if not chroma:
            if ap:
                p[1] = p1 + _clip3(-tc0, tc0,
                                   (p2 + ((p0 + q0 + 1) >> 1) - 2 * p1) >> 1)
            if aq:
                q[1] = q1 + _clip3(-tc0, tc0,
                                   (q2 + ((p0 + q0 + 1) >> 1) - 2 * q1) >> 1)
    else:                                   # bS == 4
        strong = abs(int(p0 - q0)) < (alpha >> 2) + 2
        ap = abs(int(p2 - p0)) < beta
        aq = abs(int(q2 - q0)) < beta
        if not chroma and strong and ap:
            p[0] = (p2 + 2 * p1 + 2 * p0 + 2 * q0 + q1 + 4) >> 3
            p[1] = (p2 + p1 + p0 + q0 + 2) >> 2
            p[2] = (2 * p3 + 3 * p2 + p1 + p0 + q0 + 4) >> 3
        else:
            p[0] = (2 * p1 + p0 + q1 + 2) >> 2
        if not chroma and strong and aq:
            q[0] = (q2 + 2 * q1 + 2 * q0 + 2 * p0 + p1 + 4) >> 3
            q[1] = (q2 + q1 + q0 + p0 + 2) >> 2
            q[2] = (2 * q3 + 3 * q2 + q1 + q0 + p0 + 4) >> 3
        else:
            q[0] = (2 * q1 + q0 + p1 + 2) >> 2


def _edge_v(plane, y0, x, n, bs_per_line, alpha, beta, tc0, chroma):
    """Vertical edge at column x: lines y0..y0+n-1."""
    for j in range(n):
        bs = int(bs_per_line[j])
        if bs == 0:
            continue
        row = plane[y0 + j]
        p = np.array([row[x - 1], row[x - 2], row[x - 3], row[x - 4]],
                     np.int32)
        q = np.array([row[x], row[x + 1], row[x + 2], row[x + 3]], np.int32)
        _filter_line(p, q, bs, alpha, beta, tc0, chroma)
        row[x - 3:x] = p[2::-1]
        row[x:x + 3] = q[:3]


def _edge_h(plane, x0, y, n, bs_per_line, alpha, beta, tc0, chroma):
    """Horizontal edge at row y: lines x0..x0+n-1."""
    for j in range(n):
        bs = int(bs_per_line[j])
        if bs == 0:
            continue
        col = plane[:, x0 + j]
        p = np.array([col[y - 1], col[y - 2], col[y - 3], col[y - 4]],
                     np.int32)
        q = np.array([col[y], col[y + 1], col[y + 2], col[y + 3]], np.int32)
        _filter_line(p, q, bs, alpha, beta, tc0, chroma)
        col[y - 3:y] = p[2::-1]
        col[y:y + 3] = q[:3]


def intra_bs(nr: int, nc: int):
    """bS grids for an all-intra frame under slice-per-row: vertical MB
    edges (x=0) are 4, internal edges 3; returns (bs_v (R,C,4,16),
    bs_h (R,C,3,16)) — per edge, per line."""
    bs_v = np.zeros((nr, nc, 4, 16), np.int32)
    bs_v[:, :, 1:, :] = 3
    bs_v[:, 1:, 0, :] = 4            # MB boundary (first MB: no left edge)
    bs_h = np.full((nr, nc, 3, 16), 3, np.int32)
    return bs_v, bs_h


def p_bs(nnz_blk: np.ndarray, mv: np.ndarray):
    """bS grids for a P frame (no intra MBs, one MV per MB).

    nnz_blk: (R, C, 4, 4) bool — 4x4 block has coded coefficients
    (raster [by][bx]); mv: (R, C, 2) quarter-pel.  Internal edges: 2 if
    either side has coefficients else 0 (one MV per MB -> no internal mv
    term); the x=0 MB edge adds bS=1 when the MVs differ by >= 4 quarter
    units on either axis."""
    nr, nc = nnz_blk.shape[:2]
    bs_v = np.zeros((nr, nc, 4, 16), np.int32)
    bs_h = np.zeros((nr, nc, 3, 16), np.int32)
    nnz16 = np.repeat(nnz_blk, 4, axis=2)          # (R, C, 16, 4) by-lines
    for e, bx in enumerate((1, 2, 3)):             # internal vertical
        two = (nnz16[:, :, :, bx - 1] | nnz16[:, :, :, bx]) * 2
        bs_v[:, :, e + 1, :] = two
    left_nnz = np.zeros((nr, nc, 16), bool)
    left_nnz[:, 1:] = nnz16[:, :-1, :, 3]
    mvd = np.zeros((nr, nc), bool)
    mvd[:, 1:] = (np.abs(mv[:, 1:] - mv[:, :-1]) >= 4).any(axis=-1)
    edge0 = np.where(left_nnz | nnz16[:, :, :, 0], 2,
                     np.where(mvd[:, :, None], 1, 0))
    bs_v[:, :, 0, :] = edge0
    bs_v[:, 0, 0, :] = 0                           # no left MB
    nnzx = np.repeat(nnz_blk, 4, axis=3)           # (R, C, 4, 16) bx-lines
    for e, by in enumerate((1, 2, 3)):             # internal horizontal
        bs_h[:, :, e, :] = (nnzx[:, :, by - 1] | nnzx[:, :, by]) * 2
    return bs_v, bs_h


def deblock_frame_ref(y, cb, cr, qp: int, qp_c: int, bs_v, bs_h):
    """Numpy reference: filter one frame in the spec's MB order.

    y (H, W), cb/cr (H/2, W/2) uint8; bs_v (R, C, 4, 16) vertical-edge
    bS per line, bs_h (R, C, 3, 16) internal horizontal edges (y=4,8,12).
    Returns filtered copies."""
    alpha_t, beta_t, tc0_t = load_tables()
    a_l, b_l, t_l = (int(alpha_t[qp]), int(beta_t[qp]), tc0_t[qp])
    a_c, b_c, t_c = (int(alpha_t[qp_c]), int(beta_t[qp_c]), tc0_t[qp_c])
    y = y.astype(np.int32).copy()
    cb = cb.astype(np.int32).copy()
    cr = cr.astype(np.int32).copy()
    nr, nc = bs_v.shape[:2]
    for r in range(nr):
        for c in range(nc):
            my, mx = r * 16, c * 16
            # vertical luma edges x=0,4,8,12; chroma x=0,4 (from luma 0,8)
            for e, dx in enumerate((0, 4, 8, 12)):
                if c == 0 and dx == 0:
                    continue
                _edge_v(y, my, mx + dx, 16, bs_v[r, c, e], a_l, b_l, t_l,
                        False)
            for plane in (cb, cr):
                if c > 0:
                    _edge_v(plane, my // 2, mx // 2, 8,
                            bs_v[r, c, 0, 0::2], a_c, b_c, t_c, True)
                _edge_v(plane, my // 2, mx // 2 + 4, 8,
                        bs_v[r, c, 2, 0::2], a_c, b_c, t_c, True)
            # horizontal edges y=4,8,12 (y=0 is the slice boundary);
            # chroma y=4 (from luma y=8)
            for e, dy in enumerate((4, 8, 12)):
                _edge_h(y, mx, my + dy, 16, bs_h[r, c, e], a_l, b_l, t_l,
                        False)
            for plane in (cb, cr):
                _edge_h(plane, mx // 2, my // 2 + 4, 8,
                        bs_h[r, c, 1, 0::2], a_c, b_c, t_c, True)
    clip = lambda p: np.clip(p, 0, 255).astype(np.uint8)
    return clip(y), clip(cb), clip(cr)
