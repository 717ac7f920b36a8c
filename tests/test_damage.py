"""Damage-driven encode (ISSUE 20 / ROADMAP item 3): the per-frame
device cost must track CHANGED pixels, never frame area, without the
bytes ever knowing.

Four pinned contracts:

- ONE substrate: the host-side gating grid (ops/damage_mask
  .damage_grid_np) is the exact numpy twin of the content plane's
  device damage kernel (ops/content_stats._damage_grid) — telemetry
  and gating cannot diverge.
- GOP-deep golden-decoder conformance under forced damage patterns
  (single MB, dirty row, checkerboard, full) on every masked path:
  per-frame, chunk ring, 2-way spatial mesh, and VP8 (libvpx recon
  byte-exact).
- 100%-damage byte-identity: a fully-damaged sequence through the
  mask equals the mask-off encoder bit for bit (the masked program IS
  the full program at the top of the bucket ladder).
- Compile-silence: the bucket-padded worklist re-enters compiled
  programs as the damage fraction wanders; only a NEW bucket compiles.

The damage-scaled placement properties live in test_fleet.py (fast
tier, no XLA)."""

import numpy as np
import pytest

import conftest

cv2 = pytest.importorskip("cv2")

W, H = 128, 96       # 8x6 MBs: small enough to compile fast, 6 MB rows
ROWS, COLS = H // 16, W // 16


def _psnr(a, b):
    mse = np.mean((np.asarray(a, np.float64)
                   - np.asarray(b, np.float64)) ** 2)
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


def _damage_frames(n, pattern, h=H, w=W, seed=11):
    """Frame sequence with CONTROLLED damage: each frame is the
    previous one with only the pattern's region replaced by fresh
    noise, so the ingest-luma diff — and with it the damage grid — is
    exactly the pattern."""
    r = np.random.default_rng(seed)
    rows, cols = h // 16, w // 16
    f = conftest.make_test_frame(h, w, seed=seed)
    out = [f.copy()]

    def noise(hh, ww):
        return r.integers(0, 256, (hh, ww, 3)).astype(np.uint8)

    for i in range(1, n):
        f = f.copy()
        if pattern == "single-mb":
            mr, mc = i % rows, (3 * i) % cols
            f[mr * 16:(mr + 1) * 16, mc * 16:(mc + 1) * 16] = noise(16, 16)
        elif pattern == "dirty-row":
            mr = i % rows
            f[mr * 16:(mr + 1) * 16] = noise(16, w)
        elif pattern == "checkerboard":
            for mr in range(rows):
                for mc in range(cols):
                    if (mr + mc + i) % 2 == 0:
                        f[mr * 16:(mr + 1) * 16,
                          mc * 16:(mc + 1) * 16] = noise(16, 16)
        elif pattern == "full":
            f = noise(h, w)
        else:
            raise AssertionError(pattern)
        out.append(f)
    return out


def _drive(enc, frames):
    depth = getattr(enc, "pipeline_depth", 2)
    out, pend = [], []
    for f in frames:
        pend.append(enc.encode_submit(f))
        while len(pend) >= depth:
            out.append(enc.encode_collect(pend.pop(0)))
    while pend:
        out.append(enc.encode_collect(pend.pop(0)))
    return out


_KW = dict(entropy="device", host_color=True)


# -- one substrate ---------------------------------------------------------

class TestOneSubstrate:
    def test_host_twin_equals_device_grid(self):
        """damage_grid_np == the content plane's device kernel, MB for
        MB, including sub-threshold ticks landing on the same side."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.obs import content as obsc
        from docker_nvidia_glx_desktop_tpu.ops import content_stats as cs
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg

        thr = obsc.damage_thr_sad()
        r = np.random.default_rng(5)
        for case in range(4):
            prev = r.integers(0, 256, (H, W)).astype(np.uint8)
            y = prev.copy()
            for _ in range(1 + case * 3):          # a few dirty MBs
                mr, mc = int(r.integers(ROWS)), int(r.integers(COLS))
                y[mr * 16:(mr + 1) * 16, mc * 16:(mc + 1) * 16] = \
                    r.integers(0, 256, (16, 16)).astype(np.uint8)
            y[1, 1] ^= 1                           # sub-threshold tick
            host = dmg.damage_grid_np(y, prev, thr)
            dev = np.asarray(cs._damage_grid(
                jnp.asarray(y), jnp.asarray(prev), thr))
            np.testing.assert_array_equal(host, dev)

    @pytest.mark.parametrize("planes", ["random", "saturating", "ticks"])
    def test_cheap_grid_equals_the_int64_form(self, planes, monkeypatch):
        """The uint8 / uint16 / uint32 grid (cv2's absolute difference,
        and numpy's without cv2) against the form it replaced: random
        planes, planes of 0 against 255 (every sum at its largest, 65,280
        a macroblock), and differences that land on both sides of the
        threshold by one."""
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg

        def old(y, prev, thr):
            d = np.abs(y.astype(np.int64) - prev.astype(np.int64))
            sad = d.reshape(ROWS, 16, COLS, 16).sum(axis=(1, 3))
            return (sad > thr).astype(np.uint8)

        r = np.random.default_rng(8)
        if planes == "random":
            y, prev = (r.integers(0, 256, (H, W)).astype(np.uint8)
                       for _ in range(2))
            y[:32] = prev[:32]                     # calm rows too
        elif planes == "saturating":
            y = np.where(r.integers(0, 2, (H, W)) > 0, 255, 0).astype(
                np.uint8)
            prev = (255 - y).astype(np.uint8)
            prev[16:48, 32:96] = y[16:48, 32:96]
        else:
            prev = r.integers(0, 250, (H, W)).astype(np.uint8)
            y = prev.copy()
            y[0, :16] += 2                         # 32: under thr 33 ...
            y[16, :16] += 2
            y[17, 0] += 2                          # ... 34: over it
        for thr in (33, 512, 65279, 65280):
            want = old(y, prev, thr)
            np.testing.assert_array_equal(
                dmg.damage_grid_np(y, prev, thr), want)
            with monkeypatch.context() as mp:
                mp.setattr(dmg, "_cv2", None)
                np.testing.assert_array_equal(
                    dmg.damage_grid_np(y, prev, thr), want)
        assert old(y, prev, 33).any() and not old(y, prev, 65280).any()

    def test_stream_start_marks_everything_damaged(self):
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg
        y = np.zeros((H, W), np.uint8)
        assert dmg.damage_grid_np(y, None).all()

    def test_plan_rows_bucket_ladder(self):
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg
        grid = np.zeros((ROWS, COLS), np.uint8)
        plan = dmg.plan_rows(grid)                 # calm: still 1 row
        assert plan.bucket == 1 and plan.rows.tolist() == [0]
        grid[2, 3] = 1
        grid[4, 0] = 1
        grid[5, 7] = 1
        plan = dmg.plan_rows(grid)                 # 3 rows -> bucket 4
        assert plan.rows.tolist() == [2, 4, 5]
        assert plan.bucket == 4 and not plan.full
        assert plan.padded.tolist() == [2, 4, 5, 5]   # pad = last row
        plan = dmg.plan_rows(np.ones((ROWS, COLS), np.uint8))
        assert plan.full and plan.bucket == ROWS

    def test_damage_factor_floor(self):
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask as dmg
        assert dmg.damage_factor(None) == 1.0
        assert dmg.damage_factor(1.0, floor=0.35) == pytest.approx(1.0)
        assert dmg.damage_factor(0.0, floor=0.35) == pytest.approx(0.35)
        assert dmg.damage_factor(0.5, floor=0.2) == pytest.approx(0.6)
        assert dmg.damage_factor(7.0, floor=0.2) == 1.0   # clamped


# -- GOP-deep golden-decoder conformance ----------------------------------

class TestGoldenDecodeMasked:
    """The conformant FFmpeg decoder must track the source through
    GOP-deep masked streams: device rows interleaved with host-cached
    all-skip slices must reconstruct bit-coherently frame after frame
    (any recon/skip desync compounds across a GOP and craters PSNR)."""

    @pytest.mark.parametrize(
        "pattern", ["single-mb", "dirty-row", "checkerboard", "full"])
    def test_per_frame_masked(self, pattern, tmp_path):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _damage_frames(12, pattern)
        enc = H264Encoder(W, H, gop=8, damage_mask=True, **_KW)
        efs = _drive(enc, frames)
        assert [e.keyframe for e in efs] == [i % 8 == 0
                                             for i in range(12)]
        decs = _decode_all(b"".join(e.data for e in efs), tmp_path)
        assert len(decs) == len(frames)
        for i, (d, f) in enumerate(zip(decs, frames)):
            assert _psnr(_luma(d), _luma(f)) > 30, \
                f"{pattern}: frame {i} decode mismatch"

    @pytest.mark.parametrize("pattern", ["single-mb", "checkerboard"])
    def test_chunk_ring_masked(self, pattern, tmp_path):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _damage_frames(13, pattern)
        enc = H264Encoder(W, H, gop=9, superstep_chunk=4,
                          damage_mask=True, **_KW)
        assert enc._ring_chunk == 4
        efs = _drive(enc, frames)
        decs = _decode_all(b"".join(e.data for e in efs), tmp_path)
        assert len(decs) == len(frames)
        for i, (d, f) in enumerate(zip(decs, frames)):
            assert _psnr(_luma(d), _luma(f)) > 30, \
                f"{pattern}: frame {i} decode mismatch"

    @pytest.mark.parametrize("pattern", ["dirty-row", "checkerboard"])
    def test_spatial2_masked(self, pattern, tmp_path):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        frames = _damage_frames(10, pattern)
        enc = H264Encoder(W, H, gop=8, spatial_shards=2,
                          damage_mask=True, **_KW)
        assert enc._spatial_nx == 2
        efs = _drive(enc, frames)
        decs = _decode_all(b"".join(e.data for e in efs), tmp_path)
        assert len(decs) == len(frames)
        for i, (d, f) in enumerate(zip(decs, frames)):
            assert _psnr(_luma(d), _luma(f)) > 30, \
                f"{pattern}: frame {i} decode mismatch"

    def test_calm_frames_shrink_to_skip_slices(self):
        """The wire-visible half of the perf claim: a P frame whose
        only damage is one MB must be a small fraction of a fully-
        damaged P frame (the other rows are ~4-byte skip slices)."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        calm = _damage_frames(6, "single-mb")
        noisy = _damage_frames(6, "full")
        a = _drive(H264Encoder(W, H, gop=8, damage_mask=True, **_KW),
                   calm)
        b = _drive(H264Encoder(W, H, gop=8, damage_mask=True, **_KW),
                   noisy)
        calm_p = sum(len(e.data) for e in a if not e.keyframe)
        noisy_p = sum(len(e.data) for e in b if not e.keyframe)
        assert calm_p * 4 < noisy_p


# -- 100%-damage byte-identity --------------------------------------------

class TestByteIdentity100:
    """Fresh noise every frame = every MB damaged = the masked encoder
    must take its full-frame fallback and emit EXACTLY the mask-off
    bytes, on every path."""

    def _identical(self, mk):
        frames = _damage_frames(9, "full")
        ra = _drive(mk(True), frames)
        rb = _drive(mk(False), frames)
        assert len(ra) == len(rb) == len(frames)
        for i, (x, y) in enumerate(zip(ra, rb)):
            assert x.keyframe == y.keyframe, f"frame {i} keyframe"
            assert x.data == y.data, f"frame {i} AU diverges"

    def test_per_frame(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        self._identical(lambda m: H264Encoder(
            W, H, gop=8, damage_mask=m, **_KW))

    def test_chunk_ring(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        self._identical(lambda m: H264Encoder(
            W, H, gop=9, superstep_chunk=4, damage_mask=m, **_KW))

    def test_spatial2(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        self._identical(lambda m: H264Encoder(
            W, H, gop=8, spatial_shards=2, damage_mask=m, **_KW))

    @pytest.mark.parametrize("tune", ["off", "hq"])
    def test_vp8(self, tune):
        from docker_nvidia_glx_desktop_tpu.models.vp8 import Vp8Encoder
        frames = _damage_frames(7, "full")
        a = Vp8Encoder(W, H, q_index=30, gop=8, tune=tune,
                       damage_mask=True)
        b = Vp8Encoder(W, H, q_index=30, gop=8, tune=tune,
                       damage_mask=False)
        for i, f in enumerate(frames):
            ea, eb = a.encode(f), b.encode(f)
            assert ea.keyframe == eb.keyframe
            assert ea.data == eb.data, f"frame {i} diverges"


# -- VP8 masked conformance (libvpx is the golden decoder) ----------------

class TestVp8Masked:
    @pytest.mark.parametrize("tune", ["off", "hq"])
    def test_masked_recon_byte_exact(self, tune):
        """Calm masked inter frames: libvpx reconstruction must equal
        the encoder's recon byte for byte — inactive MBs carry zero
        tokens, so the decoder rebuilds prediction exactly."""
        from docker_nvidia_glx_desktop_tpu.models.vp8 import Vp8Encoder
        from docker_nvidia_glx_desktop_tpu.native import vpx
        if not vpx.available():
            pytest.skip("libvpx not present")

        frames = _damage_frames(7, "single-mb", seed=4)
        enc = Vp8Encoder(W, H, q_index=30, gop=16, tune=tune,
                         damage_mask=True)
        dec = vpx.Vp8Decoder()
        try:
            for i, f in enumerate(frames):
                ef = enc.encode(f)
                dy, du, dv = dec.decode(ef.data)
                ry, ru, rv = enc._ref
                np.testing.assert_array_equal(
                    dy, ry[:H, :W], err_msg=f"frame {i} luma")
                np.testing.assert_array_equal(
                    du, ru[:H // 2, :W // 2], err_msg=f"frame {i} cb")
                np.testing.assert_array_equal(
                    dv, rv[:H // 2, :W // 2], err_msg=f"frame {i} cr")
                assert _psnr(dy, _luma(f)[:H, :W]) > 30
        finally:
            dec.close()


# -- compile-silence of the bucket ladder ---------------------------------

class TestDamageRetrace:
    def test_bucket_wander_is_compile_silent(self):
        """Steady-state serving with the damage fraction wandering
        inside warmed buckets must not retrace; only a NEW bucket
        compiles (exactly the power-of-two ladder claim)."""
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)
        if not compile_events_supported():
            pytest.skip("jax.monitoring unavailable")
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        def rows_frames(n_rows, n, seed):
            # n frames each dirtying exactly n_rows MB rows
            r = np.random.default_rng(seed)
            f = conftest.make_test_frame(H, W, seed=2)
            out = []
            for _ in range(n):
                f = f.copy()
                for mr in range(n_rows):
                    f[mr * 16:(mr + 1) * 16] = r.integers(
                        0, 256, (16, W, 3)).astype(np.uint8)
                out.append(f)
            return out

        enc = H264Encoder(W, H, gop=600, damage_mask=True, **_KW)
        warm = (rows_frames(1, 3, 5)       # IDR + bucket-1 P
                + rows_frames(2, 3, 6))    # bucket-2 P
        for f in warm:
            enc.encode(f)
        with RetraceTripwire(label="damage bucket wander") as tw:
            for f in rows_frames(1, 2, 7) + rows_frames(2, 2, 8):
                enc.encode(f)
        tw.assert_quiet()
        with RetraceTripwire(label="new damage bucket") as tw2:
            for f in rows_frames(3, 2, 9):    # 3 rows -> bucket 4
                enc.encode(f)
        assert tw2.compiles >= 1, \
            "bucket-4 worklist should have compiled a fresh program"
