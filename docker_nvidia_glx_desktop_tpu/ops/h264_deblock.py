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
  REWRITES the last columns of MB n-1 after n-1 finished), which maps to
  the same 120-step `lax.scan` the intra encoder uses, vectorized over
  all rows.
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
literally; the device scan is byte-identity-tested against it.
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
# Device implementation: one lax.scan over MB columns (the spec's
# left-to-right order inside each row; all MB rows vectorized), edges
# filtered as fully-vectorized line bundles.
# ---------------------------------------------------------------------------

def _filter_lines(p, q, bs, alpha, beta, tc0_row, chroma: bool):
    """Vectorized spec 8.7.2.3/8.7.2.4 over line bundles.

    p, q: (..., 4) int32 with index 0 nearest the edge; bs: (...,) int32.
    alpha/beta ints, tc0_row (3,).  Returns (p_new, q_new) with only
    indices 0..2 possibly changed."""
    import jax.numpy as jnp

    p0, p1, p2, p3 = (p[..., i] for i in range(4))
    q0, q1, q2, q3 = (q[..., i] for i in range(4))
    fil = ((jnp.abs(p0 - q0) < alpha) & (jnp.abs(p1 - p0) < beta)
           & (jnp.abs(q1 - q0) < beta) & (bs > 0))
    ap = jnp.abs(p2 - p0) < beta
    aq = jnp.abs(q2 - q0) < beta

    # --- bS < 4 normal filter ---
    if isinstance(tc0_row, np.ndarray):     # static qp: folded constants
        tc0_row = [int(v) for v in tc0_row]
    t0 = jnp.where(bs <= 1, tc0_row[0],
                   jnp.where(bs == 2, tc0_row[1], tc0_row[2]))
    tc = t0 + (1 if chroma
               else 0) + (0 if chroma
                          else ap.astype(jnp.int32) + aq.astype(jnp.int32))
    delta = jnp.clip(((q0 - p0) * 4 + (p1 - q1) + 4) >> 3, -tc, tc)
    n_p0 = jnp.clip(p0 + delta, 0, 255)
    n_q0 = jnp.clip(q0 - delta, 0, 255)
    if chroma:
        n_p1, n_q1, n_p2, n_q2 = p1, q1, p2, q2
    else:
        dp1 = jnp.clip((p2 + ((p0 + q0 + 1) >> 1) - 2 * p1) >> 1, -t0, t0)
        dq1 = jnp.clip((q2 + ((p0 + q0 + 1) >> 1) - 2 * q1) >> 1, -t0, t0)
        n_p1 = jnp.where(ap, p1 + dp1, p1)
        n_q1 = jnp.where(aq, q1 + dq1, q1)
        n_p2, n_q2 = p2, q2

    # --- bS == 4 strong filter ---
    strong = jnp.abs(p0 - q0) < ((alpha >> 2) + 2)
    s_p0w = (2 * p1 + p0 + q1 + 2) >> 2
    s_q0w = (2 * q1 + q0 + p1 + 2) >> 2
    if chroma:
        s_p0, s_p1, s_p2 = s_p0w, p1, p2
        s_q0, s_q1, s_q2 = s_q0w, q1, q2
    else:
        use_p = strong & ap
        use_q = strong & aq
        s_p0 = jnp.where(use_p,
                         (p2 + 2 * p1 + 2 * p0 + 2 * q0 + q1 + 4) >> 3,
                         s_p0w)
        s_p1 = jnp.where(use_p, (p2 + p1 + p0 + q0 + 2) >> 2, p1)
        s_p2 = jnp.where(use_p,
                         (2 * p3 + 3 * p2 + p1 + p0 + q0 + 4) >> 3, p2)
        s_q0 = jnp.where(use_q,
                         (q2 + 2 * q1 + 2 * q0 + 2 * p0 + p1 + 4) >> 3,
                         s_q0w)
        s_q1 = jnp.where(use_q, (q2 + q1 + q0 + p0 + 2) >> 2, q1)
        s_q2 = jnp.where(use_q,
                         (2 * q3 + 3 * q2 + q1 + q0 + p0 + 4) >> 3, q2)

    bs4 = bs == 4
    o_p0 = jnp.where(bs4, s_p0, n_p0)
    o_p1 = jnp.where(bs4, s_p1, n_p1)
    o_p2 = jnp.where(bs4, s_p2, n_p2)
    o_q0 = jnp.where(bs4, s_q0, n_q0)
    o_q1 = jnp.where(bs4, s_q1, n_q1)
    o_q2 = jnp.where(bs4, s_q2, n_q2)

    sel = lambda n, o: jnp.where(fil, n, o)
    import jax.numpy as _j
    p_new = _j.stack([sel(o_p0, p0), sel(o_p1, p1), sel(o_p2, p2), p3],
                     axis=-1)
    q_new = _j.stack([sel(o_q0, q0), sel(o_q1, q1), sel(o_q2, q2), q3],
                     axis=-1)
    return p_new, q_new


def _edge_v_mb(mb, x, bs, alpha, beta, tc0, chroma):
    """Filter the vertical edge at column ``x`` of (..., n, W) in place."""
    import jax.numpy as jnp

    p = jnp.stack([mb[..., x - 1 - k] for k in range(4)], axis=-1)
    q = jnp.stack([mb[..., x + k] for k in range(4)], axis=-1)
    p, q = _filter_lines(p, q, bs, alpha, beta, tc0, chroma)
    for k in range(3):
        mb = mb.at[..., x - 1 - k].set(p[..., k])
        mb = mb.at[..., x + k].set(q[..., k])
    return mb


def _edge_h_mb(mb, y, bs, alpha, beta, tc0, chroma):
    """Filter the horizontal edge at row ``y`` of (..., H, W) in place."""
    import jax.numpy as jnp

    p = jnp.stack([mb[..., y - 1 - k, :] for k in range(4)], axis=-1)
    q = jnp.stack([mb[..., y + k, :] for k in range(4)], axis=-1)
    p, q = _filter_lines(p, q, bs, alpha, beta, tc0, chroma)
    for k in range(3):
        mb = mb.at[..., y - 1 - k, :].set(p[..., k])
        mb = mb.at[..., y + k, :].set(q[..., k])
    return mb


import jax as _jax


@functools.partial(_jax.jit, static_argnames=("qp", "_group"))
def deblock_frame(y, cb, cr, qp: int, nnz_blk=None, mv=None,
                  _group: int = 0):
    """Device loop filter for one frame (slice-per-row, idc=2 edges).

    y (H, W), cb/cr (H/2, W/2) uint8 recon planes.  Intra frames pass
    nnz_blk=None (static bS: 4 at MB edges, 3 internal); P frames pass
    nnz_blk (R, C, 4, 4) bool and mv (R, C, 2) quarter-pel.  Returns
    filtered uint8 planes.  Byte-identical to :func:`deblock_frame_ref`
    (tested).

    ``_group``: MB columns per scan step (0 = auto).  The left-to-right
    MB order is a true sample dependency — MB n's x=0 edge rewrites MB
    n-1's last columns AFTER n-1 finished — so the dependency chain is
    irreducible, but each ``lax.scan`` step carries fixed overhead
    (carry shuffling + fusion dispatch), and at 4K the two 120+-step
    column scans cost ~8.7 ms (BENCH_r05).  The wavefront restructure
    runs GROUPS of columns per step with the in-group chain statically
    unrolled: the op sequence is identical (byte-exact, tested against
    ``_group=1`` and the numpy reference), the fusions are group-times
    wider, and the scan shrinks to nc/group steps."""
    import jax
    import jax.numpy as jnp

    from . import quant as _q

    with jax.named_scope("dngd.deblock_bs"):
        alpha_t, beta_t, tc0_t = load_tables()
        if _q._is_static_qp(qp):
            qp_c = _q.chroma_qp(qp)
            a_l, b_l, t_l = int(alpha_t[qp]), int(beta_t[qp]), tc0_t[qp]
            a_c, b_c, t_c = (int(alpha_t[qp_c]), int(beta_t[qp_c]),
                             tc0_t[qp_c])
        else:
            # traced slice qp (deblock_frame_dynqp): the thresholds are
            # table gathers instead of folded constants — same integers
            qp_c = _q.chroma_qp_v(qp)
            alpha_a, beta_a = jnp.asarray(alpha_t), jnp.asarray(beta_t)
            tc0_a = jnp.asarray(tc0_t)
            a_l, b_l, t_l = alpha_a[qp], beta_a[qp], tc0_a[qp]
            a_c, b_c, t_c = alpha_a[qp_c], beta_a[qp_c], tc0_a[qp_c]
        H, W = y.shape
        nr, nc = H // 16, W // 16
        intra = nnz_blk is None

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
            bs_mb0 = bs_mb0.at[:, 0].set(0)
            nnz16x = jnp.repeat(nnz_blk.astype(jnp.int32), 4, axis=3)
            bs_h_int = jnp.stack(
                [(nnz16x[:, :, by - 1] | nnz16x[:, :, by]) * 2
                 for by in (1, 2, 3)], axis=2)                 # (R, C, 3, 16)
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

    # Auto group: the wavefront amortizes the PER-STEP cost of a scan
    # iteration (fusion dispatch + carry shuffling), which is what the
    # ~8.7 ms column scans at 4K are made of on an accelerator backend.
    # The CPU backend has no such per-step cost and measured the wider
    # steps 1.5x SLOWER (BENCH_r06 profile), so auto keeps the column
    # scan there; pass ``_group`` explicitly to override either way.
    if _group:
        group = _group
    elif _jax.default_backend() == "cpu":
        group = 1
    else:
        group = next(g for g in (8, 6, 5, 4, 3, 2, 1) if nc % g == 0)

    def col_step(carry, xs):
        yl, cbl, crl = carry            # left MB last-4 columns, post-H
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
            wide = _edge_v_mb(wide, 4, bs0, a_l, b_l, t_l, False)
            for e, x in enumerate((4, 8, 12)):
                wide = _edge_v_mb(wide, 4 + x, bsv[e], a_l, b_l, t_l,
                                  False)
            left_fin = wide[..., :4]    # left MB cols 12..15, FINAL
            own = wide[..., 4:]
        with jax.named_scope("dngd.deblock_h"):
            for e, yy_ in enumerate((4, 8, 12)):
                own = _edge_h_mb(own, yy_, bsh[e], a_l, b_l, t_l, False)

        # --- chroma: MB edge + internal x=4 (luma x=8), h y=4 (luma 8) --
        def chroma_mb(mbp, left):
            with jax.named_scope("dngd.deblock_v"):
                w2 = jnp.concatenate([left, mbp], axis=-1)  # (R, 8, 12)
                w2 = _edge_v_mb(w2, 4, bs0[:, 0::2], a_c, b_c, t_c, True)
                w2 = _edge_v_mb(w2, 8, bsv[1][:, 0::2], a_c, b_c, t_c,
                                True)
                lf, ownp = w2[..., :4], w2[..., 4:]
            with jax.named_scope("dngd.deblock_h"):
                ownp = _edge_h_mb(ownp, 4, bsh[1][:, 0::2], a_c, b_c, t_c,
                                  True)
            return lf, ownp

        cbl_fin, cb_own = chroma_mb(cbmb, cbl)
        crl_fin, cr_own = chroma_mb(crmb, crl)

        carry = (own[..., -4:], cb_own[..., -4:], cr_own[..., -4:])
        out = (left_fin[..., 1:], own[..., :13],
               cbl_fin[..., 2:], cb_own[..., :6],
               crl_fin[..., 2:], cr_own[..., :6])
        return carry, out

    def step(carry, xs_g):
        # one wavefront step: ``group`` columns chained in-body (the
        # same per-column op sequence col_step always ran, unrolled)
        outs = []
        for g in range(group):
            carry, out = col_step(carry, tuple(x[g] for x in xs_g))
            outs.append(out)
        return carry, tuple(jnp.stack(parts, 0)
                            for parts in zip(*outs))

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
        xs = tuple(x.reshape((nc // group, group) + x.shape[1:])
                   for x in xs)
        carry, outs = jax.lax.scan(step, init, xs)
        outs = tuple(o.reshape((nc,) + o.shape[2:]) for o in outs)
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
deblock_frame_dynqp = _jax.jit(deblock_frame.__wrapped__,
                               static_argnames=("_group",))


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
