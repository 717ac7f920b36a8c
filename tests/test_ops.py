"""Golden tests for the transform/quant/zigzag ops (SURVEY.md §4 unit tier)."""

import pytest
import numpy as np
import scipy.fft

from docker_nvidia_glx_desktop_tpu.ops import color, dct, quant
from docker_nvidia_glx_desktop_tpu.ops import scan as zigzag


class TestColor:
    def test_round_trip_full_range(self, test_frame):
        y, cb, cr = color.rgb_to_yuv420(test_frame, matrix="full")
        rgb = np.asarray(color.yuv420_to_rgb(y, cb, cr, matrix="full"))
        # 4:2:0 subsampling loses chroma detail; flat/gradient areas round-trip
        err = np.abs(rgb.astype(int) - test_frame.astype(int))
        assert np.median(err) <= 1.0

    def test_video_range_bounds(self, test_frame):
        y, cb, cr = color.rgb_to_yuv420(test_frame, matrix="video")
        y = np.asarray(y)
        assert y.min() >= 15.5 and y.max() <= 235.5

    def test_gray_maps_to_zero_chroma(self):
        gray = np.full((16, 16, 3), 77, dtype=np.uint8)
        _, cb, cr = color.rgb_to_yuv420(gray, matrix="full")
        np.testing.assert_allclose(np.asarray(cb), 128.0, atol=1e-3)
        np.testing.assert_allclose(np.asarray(cr), 128.0, atol=1e-3)


class TestBlocks:
    def test_to_from_blocks_inverse(self, rng):
        x = rng.normal(size=(2, 32, 48)).astype(np.float32)
        b = dct.to_blocks(x, 8, 8)
        assert b.shape == (2, 4, 6, 8, 8)
        np.testing.assert_array_equal(np.asarray(dct.from_blocks(b)), x)

    def test_block_content(self):
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        b = np.asarray(dct.to_blocks(x, 4, 4))
        np.testing.assert_array_equal(b[0, 0], x[:4, :4])
        np.testing.assert_array_equal(b[1, 1], x[4:, 4:])


class TestDCT8:
    def test_matches_scipy(self, rng):
        blocks = rng.normal(scale=64, size=(5, 8, 8)).astype(np.float32)
        ours = np.asarray(dct.dct8x8(blocks))
        ref = scipy.fft.dctn(blocks, axes=(-2, -1), norm="ortho")
        np.testing.assert_allclose(ours, ref, atol=1e-3)

    def test_inverse(self, rng):
        blocks = rng.normal(scale=64, size=(5, 8, 8)).astype(np.float32)
        rec = np.asarray(dct.idct8x8(dct.dct8x8(blocks)))
        np.testing.assert_allclose(rec, blocks, atol=1e-3)


class TestH264Transform:
    def test_forward_inverse_identity_unquantized(self, rng):
        """idct4x4 expects dequantized input; feeding W*64 (the transform's own
        gain) through the spec inverse must reproduce the residual exactly for
        the DC-flat case and within rounding generally."""
        x = rng.integers(-255, 256, size=(100, 4, 4)).astype(np.int32)
        w = np.asarray(dct.fdct4x4(x))
        # Normalisation: Cf has row gains (4, 10, 4, 10) per axis (pre-quant
        # scaling is folded into MF/V); use qp where MF*V/2^qbits ~ 64 identity
        # instead: quantize at qp=0 then dequantize and invert.
        lev = np.asarray(quant.h264_quantize_4x4(w, qp=0, intra=True))
        deq = np.asarray(quant.h264_dequantize_4x4(lev, qp=0))
        rec = np.asarray(dct.idct4x4(deq))
        assert np.abs(rec - x).max() <= 2  # qp=0 is near-lossless

    def test_quant_roundtrip_quality_degrades_with_qp(self, rng):
        x = rng.integers(-200, 201, size=(500, 4, 4)).astype(np.int32)
        errs = []
        for qp in (0, 12, 24, 36, 48):
            w = np.asarray(dct.fdct4x4(x))
            lev = np.asarray(quant.h264_quantize_4x4(w, qp=qp))
            deq = np.asarray(quant.h264_dequantize_4x4(lev, qp=qp))
            rec = np.asarray(dct.idct4x4(deq))
            errs.append(np.abs(rec - x).mean())
        assert all(a <= b + 1e-9 for a, b in zip(errs, errs[1:])), errs

    def test_hadamard_involution_scaled(self, rng):
        x = rng.integers(-100, 101, size=(7, 4, 4)).astype(np.int32)
        hh = np.asarray(dct.hadamard4x4(dct.hadamard4x4(x)))
        np.testing.assert_array_equal(hh, x * 16)
        x2 = rng.integers(-100, 101, size=(7, 2, 2)).astype(np.int32)
        hh2 = np.asarray(dct.hadamard2x2(dct.hadamard2x2(x2)))
        np.testing.assert_array_equal(hh2, x2 * 4)

    def test_chroma_qp_table(self):
        assert quant.chroma_qp(20) == 20
        assert quant.chroma_qp(30) == 29
        assert quant.chroma_qp(51) == 39


class TestZigzag:
    def test_zigzag8_known_prefix(self):
        # Standard JPEG scan starts 0, 1, 8, 16, 9, 2, 3, 10 ...
        np.testing.assert_array_equal(
            zigzag.ZIGZAG8[:8], [0, 1, 8, 16, 9, 2, 3, 10])
        assert zigzag.ZIGZAG8[-1] == 63
        assert sorted(zigzag.ZIGZAG8.tolist()) == list(range(64))

    def test_zigzag4_known_order(self):
        # H.264 4x4 zigzag: 0,1,4,8,5,2,3,6,9,12,13,10,7,11,14,15
        np.testing.assert_array_equal(
            zigzag.ZIGZAG4, [0, 1, 4, 8, 5, 2, 3, 6, 9, 12, 13, 10, 7, 11, 14, 15])

    def test_round_trip(self, rng):
        x = rng.normal(size=(3, 8, 8)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(zigzag.unzigzag(zigzag.zigzag(x, 8), 8)), x)
        x4 = rng.normal(size=(3, 4, 4)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(zigzag.unzigzag(zigzag.zigzag(x4, 4), 4)), x4)


class TestJPEGQuant:
    def test_quality_scaling_monotone(self):
        l50, _ = quant.jpeg_quality_tables(50)
        np.testing.assert_array_equal(l50, quant.JPEG_LUMA_Q)
        l90, _ = quant.jpeg_quality_tables(90)
        l10, _ = quant.jpeg_quality_tables(10)
        assert (l90 <= l50).all() and (l50 <= l10).all()

    def test_quant_dequant(self, rng):
        c = rng.normal(scale=200, size=(4, 8, 8)).astype(np.float32)
        table, _ = quant.jpeg_quality_tables(75)
        lev = np.asarray(quant.jpeg_quantize(c, table))
        deq = np.asarray(quant.jpeg_dequantize(lev, table))
        assert np.abs(deq - c).max() <= table.max() / 2 + 1


# -- the loop filter with a qp a macroblock (ENCODER_TUNE=hq, PR 48) ---------
# A plain per-line filter by the text of spec 8.7, with Tables 8-16 and 8-17
# written out: nothing of it comes from ops/.

_ALPHA = [0] * 16 + [4, 4, 5, 6, 7, 8, 9, 10, 12, 13, 15, 17, 20, 22, 25, 28,
                     32, 36, 40, 45, 50, 56, 63, 71, 80, 90, 101, 113, 127,
                     144, 162, 182, 203, 226, 255, 255]
_BETA = [0] * 16 + [2, 2, 2, 3, 3, 3, 3, 4, 4, 4, 6, 6, 7, 7, 8, 8, 9, 9, 10,
                    10, 11, 11, 12, 12, 13, 13, 14, 14, 15, 15, 16, 16, 17,
                    17, 18, 18]
_TC0 = [(0, 0, 0)] * 17 + [
    (0, 0, 1), (0, 0, 1), (0, 0, 1), (0, 0, 1), (0, 1, 1), (0, 1, 1),
    (1, 1, 1), (1, 1, 1), (1, 1, 1), (1, 1, 1), (1, 1, 2), (1, 1, 2),
    (1, 1, 2), (1, 1, 2), (1, 2, 3), (1, 2, 3), (2, 2, 3), (2, 2, 4),
    (2, 3, 4), (2, 3, 4), (3, 3, 5), (3, 4, 6), (3, 4, 6), (4, 5, 7),
    (4, 5, 8), (4, 6, 9), (5, 7, 10), (6, 8, 11), (6, 8, 13), (7, 10, 14),
    (8, 11, 16), (9, 12, 18), (10, 13, 20), (11, 15, 23), (13, 17, 25)]
_QPC = list(range(30)) + [29, 30, 31, 32, 32, 33, 34, 34, 35, 35, 36, 36, 37,
                          37, 37, 38, 38, 38, 39, 39, 39, 39]


def _plain_line(px, bs, qp, chroma):
    """One line of one edge, 8.7.2.3 / 8.7.2.4: ``px`` holds p3 p2 p1 p0 q0 q1
    q2 q3 (chroma: p1 p0 q0 q1) and is filtered in place."""
    n = len(px) // 2
    p = [int(px[n - 1 - k]) for k in range(n)]
    q = [int(px[n + k]) for k in range(n)]
    alpha, beta = _ALPHA[qp], _BETA[qp]
    if bs == 0 or not (abs(p[0] - q[0]) < alpha and abs(p[1] - p[0]) < beta
                       and abs(q[1] - q[0]) < beta):
        return
    clip = lambda lo, hi, v: max(lo, min(hi, v))
    if bs < 4:
        tc0 = _TC0[qp][bs - 1]
        ap = not chroma and abs(p[2] - p[0]) < beta
        aq = not chroma and abs(q[2] - q[0]) < beta
        tc = tc0 + 1 if chroma else tc0 + ap + aq
        d = clip(-tc, tc, (((q[0] - p[0]) << 2) + (p[1] - q[1]) + 4) >> 3)
        px[n - 1], px[n] = clip(0, 255, p[0] + d), clip(0, 255, q[0] - d)
        if ap:
            px[n - 2] = p[1] + clip(-tc0, tc0, (
                p[2] + ((p[0] + q[0] + 1) >> 1) - (p[1] << 1)) >> 1)
        if aq:
            px[n + 1] = q[1] + clip(-tc0, tc0, (
                q[2] + ((p[0] + q[0] + 1) >> 1) - (q[1] << 1)) >> 1)
        return
    small = abs(p[0] - q[0]) < (alpha >> 2) + 2
    if not chroma and small and abs(p[2] - p[0]) < beta:
        px[n - 1] = (p[2] + 2 * p[1] + 2 * p[0] + 2 * q[0] + q[1] + 4) >> 3
        px[n - 2] = (p[2] + p[1] + p[0] + q[0] + 2) >> 2
        px[n - 3] = (2 * p[3] + 3 * p[2] + p[1] + p[0] + q[0] + 4) >> 3
    else:
        px[n - 1] = (2 * p[1] + p[0] + q[1] + 2) >> 2
    if not chroma and small and abs(q[2] - q[0]) < beta:
        px[n] = (p[1] + 2 * p[0] + 2 * q[0] + 2 * q[1] + q[2] + 4) >> 3
        px[n + 1] = (p[0] + q[0] + q[1] + q[2] + 2) >> 2
        px[n + 2] = (2 * q[3] + 3 * q[2] + q[1] + q[0] + p[0] + 4) >> 3
    else:
        px[n] = (2 * q[1] + q[0] + p[1] + 2) >> 2


def _plain_deblock(y, cb, cr, qpy, intra, nnz, mv):
    """A picture of one slice a macroblock row under
    disable_deblocking_filter_idc 2, macroblock by macroblock in raster order
    (8.7): left macroblock edge and inner vertical edges, then the inner
    horizontal ones (the top edge is a slice boundary); bS by 8.7.2.1 with
    one vector a macroblock; thresholds by qPav of the two sides' QPY (chroma:
    of their QPC)."""
    y, cb, cr = (a.astype(np.int64).copy() for a in (y, cb, cr))
    nr, nc = qpy.shape

    def bs_of(r, c, r2, c2, blk, blk2, mb_edge):
        if intra[r, c] or intra[r2, c2]:
            return 4 if mb_edge else 3
        if nnz[r, c][blk] or nnz[r2, c2][blk2]:
            return 2
        return int(mb_edge and (abs(mv[r, c] - mv[r2, c2]) >= 4).any())

    for r in range(nr):
        for c in range(nc):
            for x4 in range(4):                    # vertical edges
                if x4 == 0 and c == 0:
                    continue
                cp = c - 1 if x4 == 0 else c
                qp = (int(qpy[r, cp]) + int(qpy[r, c]) + 1) >> 1
                qc = (_QPC[qpy[r, cp]] + _QPC[qpy[r, c]] + 1) >> 1
                for line in range(16):
                    b4 = line // 4
                    bs = bs_of(r, cp, r, c, (b4, 3 if x4 == 0 else x4 - 1),
                               (b4, x4), x4 == 0)
                    x = 16 * c + 4 * x4
                    _plain_line(y[16 * r + line, x - 4:x + 4], bs, qp, False)
                    if x4 % 2 == 0 and line % 2 == 0:
                        for pl in (cb, cr):
                            _plain_line(pl[8 * r + line // 2,
                                           x // 2 - 2:x // 2 + 2], bs, qc, True)
            qp, qc = int(qpy[r, c]), _QPC[qpy[r, c]]
            for y4 in (1, 2, 3):                   # inner horizontal edges
                for col in range(16):
                    b4 = col // 4
                    bs = bs_of(r, c, r, c, (y4 - 1, b4), (y4, b4), False)
                    yy = 16 * r + 4 * y4
                    _plain_line(y[yy - 4:yy + 4, 16 * c + col], bs, qp, False)
                    if y4 == 2 and col % 2 == 0:
                        for pl in (cb, cr):
                            _plain_line(pl[yy // 2 - 2:yy // 2 + 2,
                                           8 * c + col // 2], bs, qc, True)
    return tuple(a.astype(np.uint8) for a in (y, cb, cr))


class TestLoopFilterPerEdge:
    H, W = 48, 160                     # 3 x 10 macroblocks: a block of 8 + 2

    def _picture(self, rng):
        h, w = self.H, self.W
        y = (np.add.outer(np.arange(h), np.arange(w)) // 3
             + rng.integers(0, 9, (h, w))).astype(np.uint8)
        y[:, : w // 2] = rng.integers(60, 120, (h, w // 2))
        cb = rng.integers(100, 140, (h // 2, w // 2)).astype(np.uint8)
        cr = rng.integers(90, 160, (h // 2, w // 2)).astype(np.uint8)
        nr, nc = h // 16, w // 16
        nnz = rng.integers(0, 2, (nr, nc, 4, 4)).astype(bool)
        mv = rng.integers(-9, 10, (nr, nc, 2)).astype(np.int32)
        qpy = rng.integers(22, 46, (nr, nc)).astype(np.int32)
        intra = rng.integers(0, 4, (nr, nc)) == 0
        return y, cb, cr, nnz, mv, qpy, intra

    def test_the_written_out_tables_are_the_recovered_ones(self):
        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock as d
        alpha, beta, tc0 = d.load_tables()
        assert alpha.tolist() == _ALPHA and beta.tolist() == _BETA
        assert [tuple(t) for t in tc0.tolist()] == _TC0
        assert quant.QPC_TABLE.tolist() == _QPC

    @pytest.mark.parametrize("schedule", ["scan", "kernel"])
    @pytest.mark.parametrize("kind", ["p", "intra"])
    def test_a_plane_and_intra_flags_against_the_plain_filter(
            self, rng, monkeypatch, schedule, kind):
        """Thresholds by each edge's qPav over the plane, bS 4 and 3 at and
        inside an intra macroblock: both schedules (the Pallas kernel in
        interpret mode) against a per-line filter by the spec's text."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock as d

        y, cb, cr, nnz, mv, qpy, intra = self._picture(rng)
        if kind == "intra":
            intra[:] = True
            kw = {"qp_eff": jnp.asarray(qpy)}
        else:
            kw = {"nnz_blk": jnp.asarray(nnz), "mv": jnp.asarray(mv),
                  "qp_eff": jnp.asarray(qpy), "mb_intra": jnp.asarray(intra)}
        want = _plain_deblock(y, cb, cr, qpy, intra, nnz, mv)
        assert (want[0] != y).mean() > 0.03          # the filter did work
        body = jax.jit(lambda *a, **k: d.deblock_frame.__wrapped__(*a, **k))
        if schedule == "scan":
            got = body(y, cb, cr, jnp.int32(30), **kw)
        else:
            with monkeypatch.context() as mp, \
                    pltpu.force_tpu_interpret_mode():
                mp.setattr(jax, "default_backend", lambda: "tpu")
                got = body(y, cb, cr, jnp.int32(30), **kw)
        for g, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w_)

    @pytest.mark.parametrize("kind", ["p", "intra"])
    def test_with_neither_the_output_is_todays_bit_for_bit(self, rng, kind):
        """No plane and no flags: the numpy reference of the one-qp filter
        (what every tune=off cell runs), and the same bytes as a flat plane
        with no intra macroblock gives."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock as d

        y, cb, cr, nnz, mv, _, _ = self._picture(rng)
        nr, nc = self.H // 16, self.W // 16
        if kind == "p":
            kw = {"nnz_blk": jnp.asarray(nnz), "mv": jnp.asarray(mv)}
            bs = d.p_bs(nnz, mv)
            flags = {"mb_intra": jnp.zeros((nr, nc), bool)}
        else:
            kw, bs, flags = {}, d.intra_bs(nr, nc), {}
        want = d.deblock_frame_ref(y, cb, cr, 33, quant.chroma_qp(33), *bs)
        got = d.deblock_frame(y, cb, cr, 33, **kw)
        flat = d.deblock_frame_dynqp(
            y, cb, cr, jnp.int32(20), **kw, **flags,
            qp_eff=jnp.full((nr, nc), 33, jnp.int32))
        for g, f, w_ in zip(got, flat, want):
            np.testing.assert_array_equal(np.asarray(g), w_)
            np.testing.assert_array_equal(np.asarray(f), w_)
