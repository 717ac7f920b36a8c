"""P-frame (inter) path: golden-decoder validation of I+P GOP streams
(BASELINE config 4; reference envelope: NVENC inter prediction,
README.md:19-21).  The conformant FFmpeg decoder must accept the stream and
match our device-side closed-loop reconstruction."""

import numpy as np
import pytest

import conftest

cv2 = pytest.importorskip("cv2")


def _psnr(a, b):
    mse = np.mean((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)
    return 99.0 if mse == 0 else 10 * np.log10(255.0 ** 2 / mse)


def _luma(rgb):
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import color
    return np.asarray(color.rgb_to_yuv420(jnp.asarray(rgb),
                                          matrix="video")[0])


def _decode_all(data: bytes, tmp_path):
    p = tmp_path / "t.264"
    p.write_bytes(data)
    cap = cv2.VideoCapture(str(p))
    frames = []
    while True:
        ok, img = cap.read()
        if not ok:
            break
        frames.append(img[:, :, ::-1].copy())
    cap.release()
    return frames


def _moving_frames(n, h=96, w=128, step=4):
    base = conftest.make_test_frame(h, w, seed=9)
    return [np.ascontiguousarray(np.roll(base, i * step, axis=1))
            for i in range(n)]


class TestGopStream:
    def test_ipp_stream_decodes_and_tracks_motion(self, tmp_path):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(4)
        enc = H264Encoder(128, 96, qp=26, gop=8)
        efs = [enc.encode(f) for f in frames]
        assert [e.keyframe for e in efs] == [True, False, False, False]
        decs = _decode_all(b"".join(e.data for e in efs), tmp_path)
        assert len(decs) == 4
        for d, f in zip(decs, frames):
            assert _psnr(_luma(d), _luma(f)) > 30, "P frame decode mismatch"

    def test_decoder_matches_device_recon(self, tmp_path):
        """Closed loop: the conformant decoder's P-frame output must match
        our on-device reconstruction — any MC/residual/entropy bug
        desynchronizes them and compounds over the GOP."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(4)
        enc = H264Encoder(128, 96, qp=26, gop=8,
                          keep_recon=True)
        data = b""
        recons = []
        for f in frames:
            data += enc.encode(f).data
            recons.append(enc.last_recon[0][:96, :128].copy())
        decs = _decode_all(data, tmp_path)
        for d, r in zip(decs, recons):
            assert _psnr(_luma(d), r) > 40, "decoder/recon desync"

    def test_p_frames_much_smaller_on_static_content(self, tmp_path):
        """Static content: P frames must be dominated by skip runs, far
        below the VERDICT bar of >=3x bitrate reduction vs all-intra."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frame = conftest.make_test_frame(96, 128, seed=10)
        enc = H264Encoder(128, 96, qp=26, gop=8)
        sizes = [len(enc.encode(frame).data) for _ in range(4)]
        assert sizes[1] < sizes[0] / 10, sizes     # near-pure skip

        enc_moving = H264Encoder(128, 96, qp=26, gop=8)
        moving = _moving_frames(8, step=2)
        m_sizes = [len(enc_moving.encode(f).data) for f in moving]
        intra = H264Encoder(128, 96, qp=26)
        i_sizes = [len(intra.encode(f).data) for f in moving]
        assert sum(m_sizes) < sum(i_sizes) / 3, (m_sizes, i_sizes)

    def test_request_keyframe_forces_idr(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(4)
        enc = H264Encoder(128, 96, qp=26, gop=100)
        assert enc.encode(frames[0]).keyframe
        assert not enc.encode(frames[1]).keyframe
        enc.request_keyframe()
        assert enc.encode(frames[2]).keyframe     # resume semantics
        assert not enc.encode(frames[3]).keyframe

    def test_gop_boundary_emits_idr(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(5, step=2)
        enc = H264Encoder(128, 96, qp=26, gop=2)
        keys = [enc.encode(f).keyframe for f in frames]
        assert keys == [True, False, True, False, True]


class TestMotionEstimation:
    def test_me_finds_global_shift(self):
        """A pure horizontal roll must be found by the full search (even
        integer MVs): the dominant MV equals the shift."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_inter

        base = conftest.make_test_frame(64, 96, seed=12)

        def planes(rgb):
            from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
            e = H264Encoder(96, 64, host_color=True)
            return e._host_yuv420(rgb)

        y0, cb0, cr0 = planes(base)
        shifted = np.ascontiguousarray(np.roll(base, 4, axis=1))
        y1, cb1, cr1 = planes(shifted)
        out = h264_inter.encode_p_frame(
            jnp.asarray(y1), jnp.asarray(cb1), jnp.asarray(cr1),
            jnp.asarray(y0), jnp.asarray(cb0), jnp.asarray(cr0), qp=26)
        mv = np.asarray(out["mv"])
        # rolled content moves +4 in x: prediction reads from x-4, i.e.
        # dx = -16 in quarter-pel units
        inner = mv[:, 1:-1]                       # edges see wrap artifacts
        # quarter-pel range is ±(4*SEARCH_R + 7) = ±39
        dom = np.bincount((inner[..., 1] + 39).ravel()).argmax() - 39
        assert dom == -16, f"dominant dx (quarter-pel) {dom}"

    def test_halfpel_conformance_on_subpixel_motion(self, tmp_path):
        """Content shifted by half a pixel: the refine stage must pick
        odd (half-pel) MVs, and the conformant decoder must still match
        our recon — proving the 6-tap/bilinear interpolation is normative
        (any deviation desyncs and compounds)."""
        cv2_mod = pytest.importorskip("cv2")
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        h, w = 96, 128
        big = conftest.make_test_frame(2 * h, 2 * w, seed=13)
        big = cv2_mod.GaussianBlur(big, (5, 5), 1.2)  # band-limit for clean
        frames = []                                   # sub-pixel sampling
        # BOTH directions: negative sub-pel motion exercises the signed
        # half-offset window selection in the quarter stage (a parity-only
        # mapping aliases off=-1 onto +1, one full pel away)
        for k in (0, 1, 2, -1, -3):
            shifted = np.roll(big, k, axis=1)         # k/2 px at full res
            frames.append(cv2_mod.resize(shifted, (w, h),
                                         interpolation=cv2_mod.INTER_AREA))

        enc = H264Encoder(w, h, qp=24, gop=8, keep_recon=True)
        data = b""
        recons = []
        odd_mvs = 0
        for f in frames:
            ef = enc.encode(f)
            data += ef.data
            recons.append(enc.last_recon[0][:h, :w].copy())
            if not ef.keyframe:
                odd_mvs += int((enc.last_mv % 4 != 0).sum())
        decs = _decode_all(data, tmp_path)
        assert len(decs) == 5
        assert odd_mvs > 0, "no sub-pel MV chosen on sub-pixel motion"
        for d, r in zip(decs, recons):
            assert _psnr(_luma(d), r) > 40, "sub-pel interp non-normative"

    def test_frame_num_wrap_long_gop(self, tmp_path):
        """An 18-frame GOP wraps the 4-bit frame_num (log2_max_frame_num=4);
        the conformant decoder must ride the wrap without desync."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(18, h=48, w=64, step=2)
        enc = H264Encoder(64, 48, qp=28, gop=20,
                          keep_recon=True)
        data = b""
        recons = []
        for f in frames:
            data += enc.encode(f).data
            recons.append(enc.last_recon[0][:48, :64].copy())
        assert enc._frame_num > 0 and enc._frame_num < 16
        decs = _decode_all(data, tmp_path)
        assert len(decs) == 18
        # the frames at/after the wrap (index 16+) must still match recon
        for d, r in zip(decs[15:], recons[15:]):
            assert _psnr(_luma(d), r) > 40, "desync across frame_num wrap"

    def test_pipelined_gop_matches_sync(self):
        """The pipelined submit/collect GOP path (two frames in flight,
        device-resident reference chain) must produce the exact bytes the
        synchronous path does."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _moving_frames(6, step=2)
        sync = H264Encoder(128, 96, qp=26, gop=4)
        want = [sync.encode(f).data for f in frames]

        pipe = H264Encoder(128, 96, qp=26, gop=4)
        got = []
        pending = []
        i = 0
        while len(got) < len(frames):
            while i < len(frames) and len(pending) < 2:
                pending.append(pipe.encode_submit(frames[i]))
                i += 1
            got.append(pipe.encode_collect(pending.pop(0)).data)
        assert [len(g) for g in got] == [len(w) for w in want]
        assert got == want

    def test_device_p_entropy_matches_host(self):
        """The device P-frame CAVLC (ops/cavlc_p_device) must be
        byte-identical to the Python reference across content mixes:
        moving (mvd coding), static (pure skip runs), mixed cbp, and a
        qp extreme."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        cases = [
            (_moving_frames(3, step=4), 26),
            ([conftest.make_test_frame(96, 128, seed=20)] * 3, 26),  # static
            (_moving_frames(3, step=2), 40),
        ]
        for frames, qp in cases:
            dev = H264Encoder(128, 96, qp=qp, gop=8,
                              entropy="device")
            host = H264Encoder(128, 96, qp=qp, gop=8,
                               entropy="python")
            for i, f in enumerate(frames):
                d = dev.encode(f)
                h = host.encode(f)
                assert d.data == h.data, (
                    f"device/host P divergence at frame {i}, qp {qp}")

    def test_rate_controller_converges(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import RateController

        rc = RateController(base_qp=26, bitrate_kbps=1000, fps=30)
        target = rc.target_bits
        # feed frames 4x over budget: qp must rise
        for _ in range(10):
            rc.update(target * 4)
        assert rc.qp > 26
        for _ in range(30):
            rc.update(target / 8)
        assert rc.qp < 26


class TestVbvRateControl:
    """Leaky-bucket VBV control (VERDICT r2 weak #3 / next-round #8): the
    controller must bound intra bursts through scene cuts, not just track
    the long-term average."""

    @staticmethod
    def _content_model(rc, kf, qp, k):
        # standard size model: bits halve per +6 qp; intra 5x a P frame
        return k * (5.0 if kf else 1.0) * 2.0 ** (-(qp - 26) / 6.0)

    def test_vbv_bounds_intra_burst_through_scene_cut(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import RateController

        rc = RateController(base_qp=26, bitrate_kbps=2000, fps=30)
        t = rc.target_bits
        k = t  # calm content: P frames on budget at base qp
        worst_level = 0.0
        gop = 30
        for i in range(300):
            if i == 150:
                k = t * 6           # scene cut: content cost jumps 6x
            kf = i % gop == 0
            qp = rc.qp_for(kf)
            bits = self._content_model(rc, kf, qp, k)
            rc.update(bits)
            if i > 30:              # after warmup
                worst_level = max(worst_level, rc.level)
        # the unpredictable cut frame itself may overshoot once; the
        # bucket must then DRAIN back under capacity and stay there
        tail_level = rc.level
        assert tail_level <= rc.vbv_cap * 0.75, (tail_level, rc.vbv_cap)
        assert worst_level <= rc.vbv_cap * 3, worst_level
        # and after the cut the controller coarsened qp
        assert rc.qp_for(False) > 26

    def test_vbv_keyframe_qp_raised_before_overflow(self):
        """An intra frame predicted to overflow the bucket gets a coarser
        qp BEFORE encoding (the pre-encode guard, not post-hoc)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import RateController

        rc = RateController(base_qp=26, bitrate_kbps=1000, fps=30)
        t = rc.target_bits
        # establish a large intra EMA near the cap
        rc.qp_for(True)
        rc.update(rc.vbv_cap * 0.8)
        # bucket still drains; next IDR at current step would overflow
        qp_p = rc.qp_for(False)
        rc.update(t)
        qp_i = rc.qp_for(True)
        assert qp_i > qp_p, (qp_i, qp_p)

    def test_vbv_pipelined_update_attribution(self):
        """qp_for(N+1) before update(N) (pipelined serving) must not
        cross-attribute frame types."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import RateController

        rc = RateController(base_qp=26, bitrate_kbps=1000, fps=30)
        t = rc.target_bits
        rc.qp_for(True)             # IDR submitted
        rc.qp_for(False)            # P submitted (pipeline depth 2)
        rc.update(t * 5)            # IDR's bits arrive first
        rc.update(t * 0.5)          # then the P's
        # intra EMA ~5x P EMA: attribution preserved through the FIFO
        assert rc._ema[True] > 3 * rc._ema[False]

    def test_encoder_integration_bitrate_holds(self):
        """End-to-end: GOP encoder with bitrate control keeps the windowed
        rate near target on synthetic content with a scene cut."""
        import numpy as np

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        rng = np.random.default_rng(0)
        calm = conftest.make_test_frame(96, 128, seed=1)
        busy = (rng.integers(0, 2, (96, 128, 3)) * 255).astype(np.uint8)
        enc = H264Encoder(128, 96, qp=26, entropy="python",
                          gop=10, bitrate_kbps=400, fps=10)
        sizes = []
        for i in range(30):
            f = calm if i < 15 else busy     # scene cut at 15
            sizes.append(len(enc.encode(f).data))
        target_bytes_s = 400_000 / 8
        # after adaptation (last second of frames), the windowed rate must
        # land within 2x of target despite the incompressible content
        window = sum(sizes[-10:])
        assert window < 2.0 * target_bytes_s, (window, target_bytes_s)


class TestEncodeFailureRecovery:
    """A frame lost to a transient encode/collect error must not leave the
    reference chain ahead of the decoder (client-visible corruption for
    the rest of the GOP) or desync the rate controller's in-flight qp
    attribution (round-3 advisor finding, models/h264.RateController)."""

    def _enc(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        return H264Encoder(128, 96, qp=26, entropy="device",
                           gop=8, bitrate_kbps=800)

    def test_submit_failure_rolls_back_rate_and_forces_idr(self):
        enc = self._enc()
        frame = conftest.make_test_frame(96, 128, seed=5)
        enc.encode_collect(enc.encode_submit(frame))        # IDR
        n0 = enc._rate.pending_count
        orig = enc._submit_p_device

        def boom(*a, **k):
            raise RuntimeError("transient device error")

        enc._submit_p_device = boom
        with pytest.raises(RuntimeError):
            enc.encode_submit(frame)                        # P attempt
        enc._submit_p_device = orig
        assert enc._rate.pending_count == n0                # no orphan
        ef = enc.encode_collect(enc.encode_submit(frame))
        assert ef.keyframe                                  # IDR resync

    def test_collect_failure_forces_idr(self):
        enc = self._enc()
        frame = conftest.make_test_frame(96, 128, seed=6)
        enc.encode_collect(enc.encode_submit(frame))        # IDR
        tok = enc.encode_submit(frame)                      # P (ref moved)
        orig = enc._collect_p_device
        enc._collect_p_device = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("pull failed"))
        with pytest.raises(RuntimeError):
            enc.encode_collect(tok)
        enc._collect_p_device = orig
        ef = enc.encode_collect(enc.encode_submit(frame))
        assert ef.keyframe                                  # IDR resync


class TestServingLatencyFixes:
    """Round-4 GOP-serving fixes: decaying-max pull prediction and the
    qp-ladder prewarm (VERDICT round-3 items 2)."""

    def test_pull_guess_tracks_recent_max_not_last_frame(self):
        """Alternating big/small P frames must not flip the pull guess
        down after a small frame — a too-small prefix costs a serial
        second device pull (a full host<->device round trip)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        enc = H264Encoder(128, 96, qp=26, entropy="device",
                          gop=100)
        pull = enc._flat_pull["p"]
        pull.BUCKET = 4096            # bucket << frame-size delta here
        r = np.random.default_rng(0)
        noisy = r.integers(0, 256, (96, 128, 3), dtype=np.uint8)
        flat = np.full((96, 128, 3), 128, np.uint8)
        enc.encode(noisy)                      # IDR
        enc.encode(flat)                       # tiny P
        enc.encode(noisy)                      # big P
        big_guess = pull.guess
        for _ in range(3):
            enc.encode(flat)                   # small Ps follow
        assert pull.guess == big_guess         # held by the 8-frame max
        # and after the window drains, the guess adapts back down
        for _ in range(8):
            enc.encode(flat)
        assert pull.guess < big_guess

    def test_prewarm_compiles_ladder_qps(self):
        """prewarm() must hit the REAL serving jit-cache keys.  On the
        served default (tune=off, device CAVLC) qp is a traced scalar:
        the ladder has nothing to compile, and warming two explicit qps
        adds at most ONE executable."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.ops import cavlc_p_device

        enc = H264Encoder(64, 48, qp=26, entropy="device",
                          gop=60, bitrate_kbps=500)
        qps = enc.ladder_qps()
        base = {min(51, max(0, 26 + s)) for s in type(enc._rate).STEPS}
        # the ladder also covers the degradation bias variants
        expected = set(base)
        for off in enc.DEGRADE_QP_OFFSETS:
            expected |= {min(51, q + off) for q in base}
        assert qps[0] == 26 and set(qps) == expected
        assert enc._dyn_qp and enc.prewarm() == 0
        # (the static-qp and the qp-traced jit wrap one function and
        # jax counts their executables in one cache)
        before = cavlc_p_device.encode_p_cavlc_frame_dynqp._cache_size()
        assert enc.prewarm(qps=[21, 23]) == 2
        assert cavlc_p_device.encode_p_cavlc_frame_dynqp._cache_size() \
            <= before + 1
        # the serving encoder's own state was never touched
        assert enc._ref is None and enc.frame_index == 0

    def test_prewarm_static_qp_tier_compiles_per_qp(self):
        """The hq tiers keep qp static (lambda decisions are compile-time
        floats): there the executable count still grows by the qps
        warmed."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.ops import cavlc_p_device

        enc = H264Encoder(64, 48, qp=26, entropy="device",
                          gop=60, bitrate_kbps=500, tune="hq")
        assert not enc._dyn_qp
        before = cavlc_p_device.encode_p_cavlc_frame._cache_size()
        # odd qps: the even-stepped ladder around every other test's base
        # qp never compiles these
        assert enc.prewarm(qps=[21, 23]) == 2
        assert cavlc_p_device.encode_p_cavlc_frame._cache_size() \
            >= before + 2

    @pytest.mark.parametrize("qp", [4, 11, 12, 26, 47])
    def test_traced_qp_is_byte_identical_to_static_qp(self, qp):
        """The qp-traced programs the per-frame path serves from (intra,
        P, in-loop deblock) emit the bytes of the static-qp programs at
        every qp — across both luma-DC dequant branches (qp < 12) and
        the chroma-qp table's bend (qp >= 30)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        class StaticQp(H264Encoder):
            _dyn_qp = False

        frames = [np.ascontiguousarray(np.roll(
            conftest.make_test_frame(64, 96, seed=3), 3 * i, axis=1))
            for i in range(3)]
        outs = []
        for cls in (H264Encoder, StaticQp):
            enc = cls(96, 64, qp=qp, entropy="device",
                      host_color=True, gop=60, deblock=True)
            assert enc._dyn_qp is (cls is H264Encoder)
            outs.append([enc.encode(f).data for f in frames])
        assert outs[0] == outs[1]

    def test_device_entropy_overflow_is_counted(self):
        """The device coder's fallback to the host coder is no longer
        silent: every overflowed frame lands on
        dngd_encoder_entropy_overflow_total (chip_smoke.py requires 0)."""
        from docker_nvidia_glx_desktop_tpu.models import h264 as m

        frame = conftest.make_test_frame(64, 96, seed=3)
        enc = m.H264Encoder(96, 64, qp=4, entropy="device",
                            host_color=True, gop=60, deblock=True)
        before = m._M_ENTROPY_OVERFLOW.value
        au = enc.encode(frame).data       # noise band at qp 4: MB cap
        assert m._M_ENTROPY_OVERFLOW.value == before + 1
        assert len(au) > 0

    def test_prewarm_forwards_intra_modes(self):
        """ADVICE r4 (medium): with ENCODER_INTRA_MODES=full the scratch
        encoder must warm 'full'-mode executables, not 'auto' ones the
        serving encoder never uses (i16_modes is part of the traced
        graph, so the jit-cache keys differ)."""
        from unittest import mock

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        enc = H264Encoder(64, 48, qp=26, entropy="device",
                          gop=60, bitrate_kbps=500, intra_modes="full")
        seen = {}
        orig = H264Encoder.__init__

        def spy(self, *a, **kw):
            seen.update(kw)
            return orig(self, *a, **kw)

        with mock.patch.object(H264Encoder, "__init__", spy):
            enc.prewarm(qps=[25])
        assert seen.get("intra_modes") == "full"

    def test_prewarm_stop_event_aborts(self):
        import threading

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        enc = H264Encoder(64, 48, qp=26, entropy="device",
                          gop=60, bitrate_kbps=500)
        stop = threading.Event()
        stop.set()
        assert enc.prewarm(qps=[20, 22, 24], stop=stop) == 0


class TestMbWindows:
    def test_radix_select_matches_naive_gather(self):
        """The radix-decomposed per-MB window select (ME hot path) must
        reposition EXACTLY like a naive per-MB gather for every caller
        configuration — including the top hi-bucket whose mid slice
        relies on _select_axis's zero-pad branch."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_inter

        rng = np.random.default_rng(0)
        # (dlim, size) of every call site: w18 integer refine, w17
        # half/quarter planes, chroma MC; plus tiny edge configs
        for dlim, size in ((8, 18), (9, 18), (5, 10), (1, 4), (0, 4)):
            span = size + 2 * dlim
            tiles = jnp.asarray(
                rng.integers(0, 255, (3, 5, span, span), np.uint8))
            offy = jnp.asarray(
                rng.integers(-dlim, dlim + 1, (3, 5), np.int32))
            offx = jnp.asarray(
                rng.integers(-dlim, dlim + 1, (3, 5), np.int32))
            # force the extreme offsets (top/bottom buckets) into the mix
            offy = offy.at[0, 0].set(dlim).at[0, 1].set(-dlim)
            offx = offx.at[0, 0].set(dlim).at[1, 0].set(-dlim)
            got = np.asarray(h264_inter._mb_windows(
                tiles, offy, offx, dlim, size))
            tn = np.asarray(tiles)
            for r in range(3):
                for c in range(5):
                    dy = int(offy[r, c]) + dlim
                    dx = int(offx[r, c]) + dlim
                    np.testing.assert_array_equal(
                        got[r, c], tn[r, c, dy:dy + size, dx:dx + size],
                        err_msg=f"dlim={dlim} size={size} mb=({r},{c})")
