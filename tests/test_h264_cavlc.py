"""H.264 CAVLC intra path: golden-decoder validation via FFmpeg-backed cv2.

SURVEY.md §4 test strategy: "bit-exact bitstream syntax tests (decode our
H.264 output with ffmpeg and compare PSNR + conformance)".  cv2's FFMPEG
backend is the conformant reference decoder here.
"""

import numpy as np
import pytest

import conftest

cv2 = pytest.importorskip("cv2")


def _psnr(a, b):
    mse = np.mean((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)
    return 99.0 if mse == 0 else 10 * np.log10(255.0 ** 2 / mse)


def _decode(data: bytes, tmp_path, n=1):
    p = tmp_path / "t.264"
    p.write_bytes(data)
    cap = cv2.VideoCapture(str(p))
    frames = []
    for _ in range(n):
        ok, img = cap.read()
        assert ok, "reference decoder rejected our stream"
        frames.append(img[:, :, ::-1].copy())
    cap.release()
    return frames


def _luma(rgb):
    from docker_nvidia_glx_desktop_tpu.ops import color
    import jax.numpy as jnp
    return np.asarray(color.rgb_to_yuv420(jnp.asarray(rgb), matrix="video")[0])


@pytest.mark.parametrize("qp", [20, 26, 34])
def test_cavlc_decodes_and_matches_recon(tmp_path, qp):
    """The conformant decoder accepts the stream, and its output matches our
    device-side closed-loop reconstruction (the strongest correctness check:
    any entropy or recon bug desynchronizes the two)."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = conftest.make_test_frame(144, 176)
    enc = H264Encoder(176, 144, qp=qp, keep_recon=True)
    ef = enc.encode(frame)
    assert ef.keyframe
    dec = _decode(ef.data, tmp_path)[0]
    ry = enc.last_recon[0][:144, :176]
    dy = _luma(dec)
    # swscale's chroma upsampling and RGB rounding keep this from being
    # bit-exact in RGB space; in luma it must be very tight.
    assert _psnr(dy, ry) > 40, "decoder disagrees with our reconstruction"
    assert _psnr(dy, _luma(frame)) > 33 - (qp - 26) * 0.8


def test_cavlc_quality_improves_with_lower_qp(tmp_path):
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = conftest.make_test_frame(96, 128, seed=3)
    scores = []
    for qp in (16, 30, 42):
        enc = H264Encoder(128, 96, qp=qp)
        dec = _decode(enc.encode(frame).data, tmp_path)[0]
        scores.append(_psnr(_luma(dec), _luma(frame)))
    assert scores[0] > scores[1] > scores[2]


def test_cavlc_cropping_non_multiple_of_16(tmp_path):
    """Frame cropping: dimensions that are not MB multiples decode at the
    exact requested geometry (SPS frame_cropping, bitstream/h264.py)."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = conftest.make_test_frame(100, 150, seed=5)
    enc = H264Encoder(150, 100, qp=24)
    dec = _decode(enc.encode(frame).data, tmp_path)[0]
    assert dec.shape == (100, 150, 3)
    assert _psnr(_luma(dec), _luma(frame)) > 30


def test_cavlc_multi_frame_stream(tmp_path):
    """Every frame is an IDR; a 3-frame stream decodes frame-accurately."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    enc = H264Encoder(128, 96, qp=24)
    frames = [conftest.make_test_frame(96, 128, seed=s) for s in range(3)]
    data = b"".join(enc.encode(f).data for f in frames)
    decs = _decode(data, tmp_path, n=3)
    for d, f in zip(decs, frames):
        assert _psnr(_luma(d), _luma(f)) > 32


def test_flat_frame_compresses_tightly():
    """A flat gray frame must code almost entirely to skipped residuals."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = np.full((144, 176, 3), 128, np.uint8)
    enc = H264Encoder(176, 144, qp=26)
    ef = enc.encode(frame)
    # 99 MBs; flat content should need only a few bits per MB + headers
    assert len(ef.data) < 600, len(ef.data)


def test_extreme_levels_low_qp(tmp_path):
    """qp=1 on a 4x4 checkerboard produces levels beyond the 12-bit level
    escape; the level_prefix >= 16 extension (§9.2.2.1) must carry them and
    the stream must decode at high fidelity (regression: these levels
    corrupted the stream before the extension landed)."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    yy, xx = np.mgrid[0:64, 0:80]
    checker = (((yy // 4) + (xx // 4)) % 2 * 255).astype(np.uint8)
    frame = np.stack([checker] * 3, axis=-1)
    enc = H264Encoder(80, 64, qp=1)
    dec = _decode(enc.encode(frame).data, tmp_path)[0]
    assert _psnr(_luma(dec), _luma(frame)) > 38


def test_host_color_path_decodes(tmp_path):
    """host_color=True (cv2 RGB->YUV on host, YUV planes uploaded): the
    stream must decode at essentially the same fidelity as the device
    conversion — cv2's BT.601 studio-range differs only in rounding."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = conftest.make_test_frame(96, 128, seed=11)
    host = H264Encoder(128, 96, qp=24, host_color=True)
    dev = H264Encoder(128, 96, qp=24, host_color=False)
    d_host = _decode(host.encode(frame).data, tmp_path)[0]
    d_dev = _decode(dev.encode(frame).data, tmp_path)[0]
    p_host = _psnr(_luma(d_host), _luma(frame))
    p_dev = _psnr(_luma(d_dev), _luma(frame))
    assert p_host > 32
    assert abs(p_host - p_dev) < 1.0, (p_host, p_dev)
    # and the two conversions themselves agree to within rounding
    planes = host._host_yuv420(frame)
    assert planes is not None
    import jax.numpy as jnp
    from docker_nvidia_glx_desktop_tpu.ops import color
    yf, cbf, crf = color.rgb_to_yuv420(jnp.asarray(frame), matrix="video")
    assert np.abs(planes[0].astype(float)
                  - np.asarray(jnp.round(yf))).max() <= 2

def test_host_color_non_mb_geometry(tmp_path):
    """host_color with cropping (non-MB-multiple dims) pads planes edge-wise
    exactly like the device path."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    frame = conftest.make_test_frame(100, 150, seed=6)
    enc = H264Encoder(150, 100, qp=24, host_color=True)
    dec = _decode(enc.encode(frame).data, tmp_path)[0]
    assert dec.shape == (100, 150, 3)
    assert _psnr(_luma(dec), _luma(frame)) > 30


def test_h_prediction_mode(tmp_path):
    """I16x16 Horizontal prediction: content constant along x must select
    H for most MBs, compress better than DC-only, and stay conformant
    (decoder matches recon)."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
    from docker_nvidia_glx_desktop_tpu.ops import h264_device

    # rows of constant color = ideal H-pred content
    yy = np.arange(96, dtype=np.uint8)[:, None]
    frame = np.repeat((yy * 2 + 30)[:, :, None], 3, axis=2)
    frame = np.repeat(frame, 128, axis=1).reshape(96, 128, 3)

    levels = h264_device.encode_intra_frame(jnp.asarray(frame), 96, 128, 26)
    modes = np.asarray(levels["pred_mode"])
    assert (modes[:, 1:] == 1).mean() > 0.5, "H mode rarely selected"

    auto = H264Encoder(128, 96, qp=26, keep_recon=True)
    ef = auto.encode(frame)
    dec = _decode(ef.data, tmp_path)[0]
    assert _psnr(_luma(dec), auto.last_recon[0][:96, :128]) > 40

    dc_py = H264Encoder(128, 96, qp=26, entropy="python",
                        intra_modes="dc")
    assert dc_py.i16_modes == "dc"
    assert len(ef.data) < len(dc_py.encode(frame).data), \
        "H mode should beat DC-only on row-constant content"


def test_device_entropy_matches_python(tmp_path):
    """The TPU CAVLC stage (ops/cavlc_device) must be byte-identical to the
    Python reference across qp extremes — including qp=1 checkerboard
    content that drives the level_prefix escape tiers of _level_vlc."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    yy, xx = np.mgrid[0:64, 0:80]
    checker = (((yy // 4) + (xx // 4)) % 2 * 255).astype(np.uint8)
    cases = [
        (conftest.make_test_frame(96, 128, seed=7), 128, 96, 26),
        (conftest.make_test_frame(96, 128, seed=8), 128, 96, 44),
        (np.stack([checker] * 3, axis=-1), 80, 64, 1),
    ]
    for frame, w, h, qp in cases:
        dev = H264Encoder(w, h, qp=qp, entropy="device")
        py = H264Encoder(w, h, qp=qp, entropy="python")
        assert dev.encode(frame).data == py.encode(frame).data, (w, h, qp)


class TestI4x4:
    """I_NxN macroblocks: per-4x4 prediction under slice-per-row
    (ops/h264_device I4 path; reference envelope README.md:19-21 — NVENC
    codes I4x4 routinely; VERDICT r2 'what's missing' #6)."""

    @staticmethod
    def _chrome_frame(h=96, w=128):
        # window-chrome content: flat fills + sharp edges -> I4 territory
        img = np.full((h, w), 210, np.uint8)
        img[0:24, :] = 70
        img[:, 0:3] = 50
        img[:, w - 3:] = 50
        img[24:26, :] = 120
        img[26:, 64:66] = 140
        yy, xx = np.mgrid[0:h, 0:w]
        img[(xx - yy > 40) & (xx - yy < 48)] = 95
        return np.stack([img] * 3, axis=-1)

    def test_i4_selected_and_decodes(self, tmp_path):
        """I4 MBs are chosen on chrome content, the stream decodes via
        ffmpeg at high PSNR, and recon matches the decoder's output."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.ops import h264_device

        frame = self._chrome_frame()
        levels = h264_device.encode_intra_frame(jnp.asarray(frame), 96, 128, 26)
        assert np.asarray(levels["mb_i4"]).mean() > 0.2, \
            "chrome content must select I_NxN macroblocks"
        # legal modes only: left family on block row 0, vertical family below
        modes = np.asarray(levels["i4_modes"])[np.asarray(levels["mb_i4"])]
        assert set(np.unique(modes)) <= {0, 1, 2, 3, 7, 8}

        enc = H264Encoder(128, 96, qp=26, keep_recon=True)
        dec = _decode(enc.encode(frame).data, tmp_path)[0]
        assert _psnr(_luma(dec), _luma(frame)) > 38
        # decoder output must track OUR closed-loop recon (any I4
        # prediction/recon bug desynchronizes the two and would later
        # corrupt P frames referencing this IDR)
        assert _psnr(_luma(dec), enc.last_recon[0][:96, :128]) > 40

    def test_i4_device_entropy_matches_python(self):
        """Device-packed bitstream is byte-identical to the Python
        reference when I_NxN MBs are present."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome_frame()
        dev = H264Encoder(128, 96, qp=26, entropy="device")
        py = H264Encoder(128, 96, qp=26, entropy="python")
        assert dev.encode(frame).data == py.encode(frame).data

    def test_i4_bitrate_win_on_chrome(self, tmp_path):
        """On chrome content I4 must cut >= 15% of bytes at ~equal PSNR
        vs the I16-only policy (VERDICT r2 next-round #6)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome_frame()
        auto = H264Encoder(128, 96, qp=26, entropy="python")
        i16 = H264Encoder(128, 96, qp=26, entropy="python")
        i16.i16_modes = "i16"
        a = auto.encode(frame)
        b = i16.encode(frame)
        assert len(a.data) < 0.85 * len(b.data), (len(a.data), len(b.data))
        pa = _psnr(_luma(_decode(a.data, tmp_path)[0]), _luma(frame))
        pb = _psnr(_luma(_decode(b.data, tmp_path)[0]), _luma(frame))
        assert pa > pb - 1.0

    def test_i4_gop_stream_with_p_frames(self, tmp_path):
        """I4 IDR followed by P frames referencing its recon decodes."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome_frame()
        moved = np.ascontiguousarray(np.roll(frame, 3, axis=1))
        enc = H264Encoder(128, 96, qp=26, gop=4)
        efs = [enc.encode(f) for f in (frame, moved)]
        assert efs[0].keyframe and not efs[1].keyframe
        decs = _decode(b"".join(e.data for e in efs), tmp_path, n=2)
        assert len(decs) == 2
        assert _psnr(_luma(decs[1]), _luma(moved)) > 35


def test_tall_geometry_beyond_256_mb_rows(tmp_path):
    """8K-class heights (> 254 MB rows — the round-2 meta-cap limitation):
    the flat-buffer metadata now carries up to 510 rows; a 4160-tall frame
    (260 MB rows) encodes on the device path and decodes."""
    from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

    h, w = 4160, 64
    rng = np.random.default_rng(4)
    frame = np.repeat(rng.integers(0, 256, (h // 16, w, 3)), 16,
                      axis=0).astype(np.uint8)
    enc = H264Encoder(w, h, qp=30, entropy="device")
    ef = enc.encode(frame)
    dec = _decode(ef.data, tmp_path)[0]
    assert dec.shape[:2] == (h, w)
    assert _psnr(_luma(dec), _luma(frame)) > 30


class TestDeblocking:
    """Normative in-loop deblocking under slice-per-row (idc=2;
    ops/h264_deblock).  The conformant decoder applies ITS filter with
    the spec tables — agreement proves the recovered tables and filter
    are normative."""

    def test_tables_recovered(self):
        from docker_nvidia_glx_desktop_tpu.ops.h264_deblock import (
            load_tables)

        a, b, t = load_tables()
        assert a.shape == (52,) and b.shape == (52,) and t.shape == (52, 3)
        assert a[15] == 0 and a[16] == 4 and a[51] == 255
        assert b[16] == 2 and b[51] == 18
        assert tuple(t[51]) == (13, 17, 25)

    def test_intra_filtered_recon_matches_decoder(self, tmp_path):
        """Decoder output vs our loop-filtered recon must agree much more
        tightly than vs the unfiltered recon."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock

        h, w = 96, 128
        yy, xx = np.mgrid[0:h, 0:w]
        img = (100 + 60 * np.sin(xx / 19) + 50 * np.cos(yy / 23))
        frame = np.stack([img.astype(np.uint8)] * 3, -1)
        enc = H264Encoder(w, h, qp=34, keep_recon=True,
                          deblock=True)
        dec = _decode(enc.encode(frame).data, tmp_path)[0]
        dy = _luma(dec)
        ry = enc.last_recon[0]
        fy, _, _ = h264_deblock.deblock_frame(
            jnp.asarray(ry), jnp.asarray(enc.last_recon[1]),
            jnp.asarray(enc.last_recon[2]), 34)
        p_filt = _psnr(dy, np.asarray(fy)[:h, :w])
        p_unf = _psnr(dy, ry[:h, :w])
        assert p_filt > 45, (p_filt, p_unf)
        assert p_filt > p_unf + 5, (p_filt, p_unf)

    def test_gop_with_deblock_no_drift(self, tmp_path):
        """A long GOP with filtered references: if our filter deviated
        from the decoder's, the mismatch would compound frame over frame
        — late P frames must still decode at full fidelity."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        h, w = 96, 128
        base = conftest.make_test_frame(h, w, seed=21)
        frames = [np.ascontiguousarray(np.roll(base, 2 * k, axis=1))
                  for k in range(8)]
        enc = H264Encoder(w, h, qp=28, gop=8, deblock=True)
        data = b"".join(enc.encode(f).data for f in frames)
        decs = _decode(data, tmp_path, n=8)
        early = _psnr(_luma(decs[1]), _luma(frames[1]))
        late = _psnr(_luma(decs[7]), _luma(frames[7]))
        assert late > 30 and late > early - 2.0, (early, late)

    @pytest.mark.parametrize("qp", [20, 28, 36, 44])
    def test_device_filter_byte_identical_to_reference(self, qp):
        """deblock_frame (the vectorized device filter) must match
        deblock_frame_ref (spec-order numpy) EXACTLY — intra and P bS
        inputs — so long-GOP conformance isn't resting on PSNR bounds."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock, quant

        h, w = 96, 128
        nr, nc = h // 16, w // 16
        r = np.random.default_rng(qp)
        y = r.integers(0, 256, (h, w), dtype=np.uint8)
        cb = r.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)
        cr = r.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)
        qp_c = quant.chroma_qp(qp)

        # intra: static bS
        got = [np.asarray(p) for p in h264_deblock.deblock_frame(
            jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr), qp)]
        bs_v, bs_h = h264_deblock.intra_bs(nr, nc)
        want = h264_deblock.deblock_frame_ref(y, cb, cr, qp, qp_c,
                                              bs_v, bs_h)
        for g, want_p in zip(got, want):
            assert np.array_equal(g, want_p)

        # P: data-dependent bS from nnz + mv
        nnz = r.random((nr, nc, 4, 4)) < 0.5
        mv = r.integers(-12, 13, (nr, nc, 2)).astype(np.int32)
        got = [np.asarray(p) for p in h264_deblock.deblock_frame(
            jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr), qp,
            nnz_blk=jnp.asarray(nnz), mv=jnp.asarray(mv))]
        bs_v, bs_h = h264_deblock.p_bs(nnz, mv)
        want = h264_deblock.deblock_frame_ref(y, cb, cr, qp, qp_c,
                                              bs_v, bs_h)
        for g, want_p in zip(got, want):
            assert np.array_equal(g, want_p)

    def test_deblock_device_entropy_byte_identical_to_python(self):
        """idc=2 headers flow through both entropy paths identically."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = conftest.make_test_frame(96, 128, seed=3)
        dev = H264Encoder(128, 96, qp=26, entropy="device",
                          deblock=True)
        py = H264Encoder(128, 96, qp=26, entropy="python",
                         deblock=True)
        assert dev.encode(frame).data == py.encode(frame).data


class TestI4FullModes:
    """i16_modes='full': nine-mode I4x4 search on block rows 1-3
    (VERDICT r3 item 6).  I16 Vertical/Plane are NOT part of this axis:
    under slice-per-row the MB above is another slice, whose samples are
    unavailable for intra prediction (spec 6.4.9/8.3.3) — DC and
    Horizontal are the only legal I16 modes in this geometry."""

    @staticmethod
    def _chrome():
        return TestI4x4._chrome_frame()

    def test_all_nine_modes_selected_and_conformant(self, tmp_path):
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.ops import h264_device

        frame = self._chrome()
        levels = h264_device.encode_intra_frame(
            jnp.asarray(frame), 96, 128, 26, i16_modes="full")
        used = set(np.unique(
            np.asarray(levels["i4_modes"])[np.asarray(levels["mb_i4"])]))
        assert used == set(range(9)), used   # every mode exercised

        enc = H264Encoder(128, 96, qp=26, keep_recon=True,
                          intra_modes="full")
        dec = _decode(enc.encode(frame).data, tmp_path)[0]
        # decoder output tracks OUR closed-loop recon: any predictor
        # formula error desynchronizes them
        assert _psnr(_luma(dec), enc.last_recon[0][:96, :128]) > 40
        assert _psnr(_luma(dec), _luma(frame)) > 38

    @pytest.mark.parametrize("qp", [22, 30])
    def test_full_not_worse_than_auto(self, qp, tmp_path):
        """More candidates can only reduce estimated bits; assert the
        real coded size improves on chrome content (measured ~14% at
        qp 26) and both decode."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome()
        full = H264Encoder(128, 96, qp=qp,
                           intra_modes="full")
        auto = H264Encoder(128, 96, qp=qp,
                           intra_modes="auto")
        b_full = full.encode(frame).data
        b_auto = auto.encode(frame).data
        assert len(_decode(b_full, tmp_path)) == 1
        assert len(b_full) < len(b_auto), (len(b_full), len(b_auto))

    def test_full_modes_device_entropy_byte_identical(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome()
        dev = H264Encoder(128, 96, qp=26, entropy="device",
                          intra_modes="full")
        py = H264Encoder(128, 96, qp=26, entropy="python",
                         intra_modes="full")
        assert dev.encode(frame).data == py.encode(frame).data

    def test_full_modes_cabac(self, tmp_path):
        """Full mode set through the CABAC entropy path."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = self._chrome()
        cab = H264Encoder(128, 96, qp=26, entropy="cabac",
                          intra_modes="full")
        cav = H264Encoder(128, 96, qp=26, entropy="python",
                          intra_modes="full")
        d1 = _decode(cab.encode(frame).data, tmp_path)[0]
        d2 = _decode(cav.encode(frame).data, tmp_path)[0]
        assert np.array_equal(d1, d2)
