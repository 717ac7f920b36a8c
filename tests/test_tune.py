"""ENCODER_TUNE=hq conformance (ISSUE 15): per-MB adaptive quantization
(mb_qp_delta), Lagrangian mode decisions including I_16x16-in-P, and the
1-frame lookahead must produce streams a conformant decoder accepts and
tracks — across CAVLC device/python entropy, CABAC, the GOP-chunk
super-step, and the 2-shard spatial mesh — while tune=off stays strictly
opt-out (no hq code path runs).  Plus RateController mean-coded-qp
normalization properties and the retrace tripwire for hq steady state.
"""

import numpy as np
import pytest

import conftest  # noqa: F401  (forces the 8-device CPU backend)

cv2 = pytest.importorskip("cv2")

from docker_nvidia_glx_desktop_tpu.models.h264 import (  # noqa: E402
    H264Encoder, RateController)

W, H = 64, 64


def _luma(rgb):
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import color
    return np.asarray(color.rgb_to_yuv420(jnp.asarray(rgb),
                                          matrix="video")[0])


def _psnr(a, b):
    mse = np.mean((np.asarray(a, np.float64)
                   - np.asarray(b, np.float64)) ** 2)
    return 99.0 if mse == 0 else 10 * np.log10(255.0 ** 2 / mse)


def _decode_all(data: bytes, tmp_path, n):
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


def _drift_frames(n, w=W, h=H):
    """Two independently-drifting sine fields: non-translational motion
    the +-8 pel ME cannot track, so the hq Lagrangian decision codes
    I_16x16 MBs inside P slices (the class the BD-rate bench measures
    a >15% gain on)."""
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
    out = []
    for i in range(n):
        ph = i * 0.6
        g = (110 + 70 * np.sin(xx / w * 3.1 + ph)
             + 55 * np.cos(yy / h * 2.3 + 0.5 * ph))
        out.append(np.clip(np.stack([g, g * 0.9 + 10, g * 0.8 + 20],
                                    axis=-1), 0, 255).astype(np.uint8))
    return out


def _mixed_frames(n, w=W, h=H):
    """Flat background + busy texture + a scrolling bar: exercises the
    AQ plane's both signs, skip, and the lookahead bias."""
    r = np.random.default_rng(7)
    base = np.full((h, w, 3), 200, np.uint8)
    base[: h // 2, : w // 2] = r.integers(0, 256, (h // 2, w // 2, 3))
    out = []
    for i in range(n):
        f = base.copy()
        y0 = (4 * i) % (h - 8)
        f[y0: y0 + 8] = (30, 30, 40)
        out.append(f)
    return out


def _encode_gop(enc, frames):
    aus, recons = [], []
    for f in frames:
        aus.append(enc.encode(f).data)
        recons.append(np.asarray(enc.last_recon[0]))
    return aus, recons


class TestHqConformance:
    """Golden-decoder round-trips for tune=hq access units."""

    @pytest.mark.parametrize("qp", [26, 34])
    @pytest.mark.parametrize("mkframes", [_drift_frames, _mixed_frames])
    def test_hq_cavlc_gop_decodes_and_tracks_recon(self, tmp_path, qp,
                                                   mkframes):
        n = 5
        frames = mkframes(n)
        enc = H264Encoder(W, H, qp=qp, entropy="device",
                          gop=n, keep_recon=True, tune="hq")
        aus, recons = _encode_gop(enc, frames)
        dec = _decode_all(b"".join(aus), tmp_path, n)
        for i, d in enumerate(dec):
            assert _psnr(_luma(d), recons[i]) > 40, f"frame {i}"

    def test_hq_emits_intra_in_p_on_untrackable_motion(self):
        """The drift content must actually exercise the I16-in-P path
        (otherwise the conformance tests above prove nothing new)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import _yuv_stage
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_inter
        frames = _drift_frames(2)
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=2, tune="hq")
        planes = [_yuv_stage(jnp.asarray(f), enc.pad_h, enc.pad_w)
                  for f in frames]
        ref = tuple(jnp.asarray(np.asarray(p)) for p in planes[0])
        out = h264_inter.encode_p_frame(
            *planes[1], *ref, qp=30, tune="hq", p_intra=True)
        n_intra = int(np.asarray(out["mb_intra"]).sum())
        assert n_intra > 0, "no I16-in-P MBs chosen on drift content"
        # an intra MB's left neighbor is never intra (run-parity gate:
        # its DC predictor must come from an inter reconstruction)
        mi = np.asarray(out["mb_intra"])
        assert not (mi[:, 1:] & mi[:, :-1]).any()
        # intra MBs carry the zero vector in the mv plane (what the
        # spec substitutes for an intra neighbor in mv prediction)
        assert (np.asarray(out["mv"])[mi] == 0).all()

    @pytest.mark.parametrize("qp", [26, 34])
    def test_hq_device_entropy_matches_python(self, qp):
        n = 4
        frames = _drift_frames(n)
        e_dev = H264Encoder(W, H, qp=qp, entropy="device",
                            gop=n, tune="hq")
        e_py = H264Encoder(W, H, qp=qp, entropy="python",
                           gop=n, tune="hq")
        for i, f in enumerate(frames):
            a, b = e_dev.encode(f).data, e_py.encode(f).data
            assert a == b, f"frame {i}: device != python entropy"

    def test_hq_cabac_gop_decodes(self, tmp_path):
        """hq + CABAC: per-MB qp deltas ride the dense host coder (no
        I16-in-P there — the v1 gate models/h264 documents)."""
        n = 4
        frames = _mixed_frames(n)
        enc = H264Encoder(W, H, qp=30, entropy="cabac",
                          gop=n, keep_recon=True, tune="hq")
        assert not enc._p_intra
        aus, recons = _encode_gop(enc, frames)
        dec = _decode_all(b"".join(aus), tmp_path, n)
        for i, d in enumerate(dec):
            assert _psnr(_luma(d), recons[i]) > 40, f"frame {i}"

    def test_hq_noaq_tier_decodes(self, tmp_path):
        """The attribution tier (lambda decisions, flat qp plane)."""
        n = 4
        frames = _drift_frames(n)
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, keep_recon=True, tune="hq_noaq")
        aus, recons = _encode_gop(enc, frames)
        dec = _decode_all(b"".join(aus), tmp_path, n)
        for i, d in enumerate(dec):
            assert _psnr(_luma(d), recons[i]) > 40, f"frame {i}"


class TestHqExecutionShapes:
    """Chunk and spatial paths must be byte-identical to per-frame."""

    def _drive(self, enc, frames):
        out, pend = [], []
        depth = getattr(enc, "pipeline_depth", 2)
        for f in frames:
            pend.append(enc.encode_submit(f))
            while len(pend) >= depth:
                out.append(enc.encode_collect(pend.pop(0)))
        while pend:
            out.append(enc.encode_collect(pend.pop(0)))
        return [ef.data for ef in out]

    def test_hq_noaq_superstep_chunk_matches_per_frame(self):
        """Byte identity chunk vs per-frame for the lambda tier (incl.
        I16-in-P through the donated-ring scan).  The full hq tier is
        NOT byte-comparable to the unchunked path by design: its
        1-frame lookahead only exists where frames are staged (the ring
        mirror `_ring_flush` preserves identity at flush boundaries),
        so hq chunk output is covered by the conformance test below."""
        n = 9                        # IDR + 2 chunks of 4
        frames = _drift_frames(n)
        ref = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, tune="hq_noaq")
        want = [ref.encode(f).data for f in frames]
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, tune="hq_noaq", superstep_chunk=4)
        got = self._drive(enc, frames)
        for i, (a, b) in enumerate(zip(got, want)):
            assert a == b, f"frame {i}: chunk != per-frame"

    def test_hq_superstep_chunk_stream_decodes(self, tmp_path):
        """The chunked hq stream (qp plane + lookahead + I16-in-P
        through the scan) must decode and track the ring recon."""
        n = 9
        frames = _drift_frames(n)
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, tune="hq", superstep_chunk=4)
        assert enc.superstep_chunk >= 2   # ring actually eligible
        got = self._drive(enc, frames)
        dec = _decode_all(b"".join(got), tmp_path, n)
        for i, d in enumerate(dec):
            assert _psnr(_luma(d), _luma(frames[i])) > 28, f"frame {i}"

    def test_hq_spatial_2shard_matches_single(self):
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        n = 5
        frames = _drift_frames(n)
        ref = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, tune="hq")
        want = [ref.encode(f).data for f in frames]
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=n, tune="hq", spatial_shards=2)
        got = self._drive(enc, frames)
        for i, (a, b) in enumerate(zip(got, want)):
            assert a == b, f"frame {i}: 2-shard != single-device"


class TestOffTierOptOut:
    """tune=off must be strictly opt-in: no hq machinery engages."""

    def test_off_never_enables_p_intra_or_qp_map(self):
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=4, tune="off")
        assert enc.tune == "off" and enc._ktune == "off"
        assert not enc._p_intra
        frames = _mixed_frames(3)
        for f in frames:
            enc.encode(f)
        assert enc._take_mean_qp() is None   # no qp plane was produced

    @pytest.mark.parametrize("kw,served", [
        ({}, True),
        ({"entropy": "cabac"}, False),
        ({"superstep_chunk": 4}, False),
        ({"spatial_shards": 2}, False),
        ({"damage_mask": True, "host_color": True}, False),
    ], ids=["per_frame", "cabac", "ring", "mesh", "mask"])
    def test_hq_with_deblock_degrades_to_noaq_no_pintra(self, kw, served):
        """Under the loop filter the tier is served WHOLE on the per-frame
        one-chip device-CAVLC path (the qp plane and the intra escape on:
        the filter takes the effective qps and the intra flags) and
        degrades, as it did everywhere, on the paths whose filter calls
        hand over one qp a slice."""
        enc = H264Encoder(W, H, qp=30, gop=4, deblock=True, tune="hq",
                          **{"entropy": "device", **kw})
        assert enc.tune == "hq"
        if served:
            assert enc._ktune == "hq" and enc._p_intra and enc._dyn_qp
        else:
            assert enc._ktune == "hq_noaq"
            assert not enc._p_intra  # no intra bS in that path's filter
            assert not enc._dyn_qp   # compile-time lambdas: a program a qp


class TestRateControllerMeanQp:
    """The +6-qp-halves-bits model must normalize by the MEAN CODED qp
    when adaptive quantization moves the plane off the ladder value."""

    def test_norm_uses_mean_qp(self):
        rc = RateController(base_qp=30, bitrate_kbps=1000, fps=30)
        assert rc._norm(1000.0, 36) == pytest.approx(2000.0)
        assert rc._norm(1000.0, 24) == pytest.approx(500.0)
        assert rc._norm(1000.0, 30.0) == pytest.approx(1000.0)

    @pytest.mark.parametrize("delta", [-4.0, -1.5, 0.0, 2.0])
    def test_update_normalizes_by_mean_coded_qp(self, delta):
        """The size EMA must reflect the qp the frame was ACTUALLY
        coded at (the AQ plane's mean), not the nominal ladder value —
        a -4 mean delta halves-ish the equivalent-bits sample."""
        bits = 50_000
        rc = RateController(base_qp=30, bitrate_kbps=1000, fps=30)
        q = rc.qp_for(False)
        rc.update(bits, mean_qp=q + delta)
        want = bits * 2.0 ** ((q + delta - rc.base_qp) / 6.0)
        assert rc._ema[False] == pytest.approx(want, rel=1e-9)
        # and omitting mean_qp falls back to the nominal coded qp
        rc2 = RateController(base_qp=30, bitrate_kbps=1000, fps=30)
        q2 = rc2.qp_for(False)
        rc2.update(bits)
        assert rc2._ema[False] == pytest.approx(
            bits * 2.0 ** ((q2 - rc2.base_qp) / 6.0), rel=1e-9)

    def test_nonzero_mean_delta_steers_qp(self):
        """An AQ plane that codes finer than nominal (negative mean
        delta) reports fewer equivalent bits, so the controller holds a
        lower qp than one fed the nominal ladder value."""
        over = 4_000_000             # way over budget: forces upshifts
        raw = RateController(base_qp=30, bitrate_kbps=1000, fps=30)
        aq = RateController(base_qp=30, bitrate_kbps=1000, fps=30)
        for _ in range(30):
            raw.update(over, mean_qp=raw.qp_for(False))
            aq.update(over, mean_qp=aq.qp_for(False) - 4.0)
        assert aq.qp <= raw.qp


class TestHqRetrace:
    """tune=hq steady state must be compile-silent (the p_intra /
    qp-plane machinery is all static-shape device code)."""

    def test_hq_steady_state_compile_silent(self):
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)

        if not compile_events_supported():
            pytest.skip("jax.monitoring compile events unavailable")
        frames = _drift_frames(12)
        enc = H264Encoder(W, H, qp=30, entropy="device",
                          gop=6, tune="hq")
        for f in frames[:7]:         # full GOP + next IDR warm-up
            enc.encode(f)
        with RetraceTripwire(label="tune=hq steady state") as tw:
            for f in frames[7:]:
                enc.encode(f)
        tw.assert_quiet()


SW, SH = 192, 112


def _served_cfg(monkeypatch, **env):
    """The configuration ``desk1080-hq`` states, at the tests' size."""
    from docker_nvidia_glx_desktop_tpu.utils.config import from_env
    for k, v in {"SIZEW": str(SW), "SIZEH": str(SH), "REFRESH": "60",
                 "WEBRTC_ENCODER": "nvh264enc", "ENCODER_ENTROPY": "device",
                 "ENCODER_GOP": "60", "ENCODER_BITRATE_KBPS": "8000",
                 "ENCODER_TUNE": "hq", "PASSWD": "x", **env}.items():
        monkeypatch.setenv(k, v)
    return from_env()


def _busy_frames(n, w=SW, h=SH, seed=5):
    """A panned texture with one fresh macroblock in 8 (what the cell's
    ``fulldamage`` traffic does) beside a flat half: the search loses the
    fresh ones, the plane takes both signs."""
    r = np.random.default_rng(seed)
    tex = r.integers(0, 256, (h + 128, w + 128, 3), np.uint8)
    tex = cv2.GaussianBlur(tex, (0, 0), 2.0)
    out = []
    for i in range(n):
        f = tex[3 * i:3 * i + h, 7 * i:7 * i + w].copy()
        f[:, : w // 4] = 90 + (np.arange(h)[:, None, None] // 8) % 3
        for k in range((h // 16) * (w // 16)):
            if (k + i) % 8 == 0:
                my, mx = divmod(k, w // 16)
                f[16 * my:16 * my + 16, 16 * mx:16 * mx + 16] = r.integers(
                    0, 256, (16, 16, 3), np.uint8)
        out.append(f)
    return out


class TestHqServedUnderLoopFilter:
    """ENCODER_TUNE=hq through ``make_encoder`` (loop filter on, CBR): the
    deployment ``desk1080-hq`` at the tests' size."""

    def test_served_gop_is_the_decoders_picture_bit_for_bit(
            self, tmp_path, monkeypatch):
        """Every reference picture the encoder keeps is the decoder's
        picture, with a plane that is not flat and I_16x16 macroblocks in
        the P slices: a wrong qPav or bS is a wrong sample at the first
        edge it touches, and every later frame predicts from it."""
        from docker_nvidia_glx_desktop_tpu.models import make_encoder
        from docker_nvidia_glx_desktop_tpu.models import h264 as m

        enc, name = make_encoder(_served_cfg(monkeypatch), SW, SH)
        assert name == "h264_cavlc" and enc.deblock
        assert enc._hq_loop and enc._ktune == "hq" and enc._p_intra
        before = {c: c.value for c in (m._M_P_MBS, m._M_P_INTRA_MBS,
                                       m._M_CODED_QP_SUM, m._M_SLICE_QP_SUM)}
        n = 6
        data, refs, planes = enc.headers(), [], []
        for f in _busy_frames(n):
            tok = enc.encode_submit(f)
            if tok[0] == "p":
                planes.append((np.asarray(tok[4][2]["qp_map"]),
                               np.asarray(tok[4][2]["mb_intra"])))
            data += enc.encode_collect(tok).data
            refs.append(np.array(enc.export_state()["ref"][0][:SH, :SW]))
        assert len(planes) == n - 1
        assert any(len(np.unique(q)) > 2 for q, _ in planes)
        assert sum(int(i.sum()) for _, i in planes) > 0
        p = tmp_path / "hq.264"
        p.write_bytes(data)
        cap = cv2.VideoCapture(str(p))
        cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
        for i, ref in enumerate(refs):
            ok, img = cap.read()
            assert ok, f"picture {i} did not decode"
            luma = np.asarray(img).reshape(-1)[:SW * SH].reshape(SH, SW)
            assert int(np.abs(luma.astype(np.int16) - ref).max()) == 0, (
                f"picture {i}")
        # the counters the benchmark's readers take, off the meta words
        mbs = (SW // 16) * (SH // 16)
        d = {c: c.value - v for c, v in before.items()}
        assert d[m._M_P_MBS] == (n - 1) * mbs
        assert d[m._M_P_INTRA_MBS] == sum(int(i.sum()) for _, i in planes)
        assert d[m._M_SLICE_QP_SUM] > 0
        assert d[m._M_CODED_QP_SUM] != d[m._M_SLICE_QP_SUM]

    def test_one_compile_of_each_program_across_five_qps(self, monkeypatch):
        """The rate ladder's rungs (and the IDR's ``I_QP_BIAS`` twins) are
        values of a traced scalar: behind one IDR and one P frame nothing
        compiles at any other qp, and there is no ladder to prewarm."""
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)
        from docker_nvidia_glx_desktop_tpu.models import make_encoder

        if not compile_events_supported():
            pytest.skip("jax.monitoring compile events unavailable")
        enc, _ = make_encoder(_served_cfg(monkeypatch), SW, SH)
        assert enc.prewarm() == 0
        assert all(q in enc.ladder_qps() for q in (20, 26, 44))
        assert len(enc.ladder_qps()) == len(set(
            min(51, q + o) for q in {min(51, max(0, enc.qp + s))
                                     for s in RateController.STEPS}
            for o in (0,) + enc.DEGRADE_QP_OFFSETS))   # no I_QP_BIAS twins
        frames = _busy_frames(12)
        enc._forced_qp = 30
        enc.encode(frames[0])
        enc.encode(frames[1])
        with RetraceTripwire(label="hq under the loop filter") as tw:
            for i, qp in enumerate((20, 26, 33, 40, 47)):
                enc._forced_qp = qp
                enc._force_idr = True
                enc.encode(frames[2 + 2 * i])
                enc.encode(frames[3 + 2 * i])
        tw.assert_quiet()

    @pytest.mark.parametrize("env", [
        {"ENCODER_ENTROPY": "cabac"}, {"ENCODER_SUPERSTEP_CHUNK": "4"},
        {"ENCODER_SPATIAL_SHARDS": "2"}, {"DNGD_DAMAGE_MASK": "true"}],
        ids=["cabac", "ring", "mesh", "mask"])
    def test_other_paths_keep_the_parents_tier(self, monkeypatch, env):
        """hq under CABAC, the ring, the mesh and the mask is what it was: the
        lambda tier at one qp a slice, byte for byte the stream of an
        encoder BUILT as ``hq_noaq`` (the tier the parent degraded to)."""
        from docker_nvidia_glx_desktop_tpu.models import make_encoder

        frames = _busy_frames(3, W, H)
        env = dict(env, ENCODER_BITRATE_KBPS="0")    # one qp: one program
        enc, _ = make_encoder(_served_cfg(monkeypatch, **env), W, H)
        assert not enc._hq_loop and enc._ktune == "hq_noaq"
        want, _ = make_encoder(_served_cfg(
            monkeypatch, **env, ENCODER_TUNE="hq_noaq"), W, H)
        assert want._ktune == "hq_noaq"

        def drive(e):
            out, pend = [], []
            for f in frames:
                pend.append(e.encode_submit(f))
                while len(pend) >= e.pipeline_depth:
                    out.append(e.encode_collect(pend.pop(0)).data)
            while pend:
                out.append(e.encode_collect(pend.pop(0)).data)
            return out

        assert drive(enc) == drive(want)
