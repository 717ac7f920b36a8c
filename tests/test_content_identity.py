"""Content-plane inertness, GOP-deep (ISSUE 17 acceptance): with the
in-graph stats plane ON vs its master switch OFF, every encode path
must emit BYTE-IDENTICAL bitstreams — per-frame device CAVLC, CABAC
device-binarize, the super-step chunk ring, and 2-way spatial shards —
because the stats kernels only read encode inputs/outputs.  Also the
in-path consistency checks the fast tier can't do: the per-frame and
chunked stats programs agree on the same stream, stats match the host
oracle from inside the real encode path, and a calm desktop measures
LESS damage than noise."""

import numpy as np

import conftest  # noqa: F401  (forces the 8-device CPU backend)
import pytest

from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
from docker_nvidia_glx_desktop_tpu.models.vp8 import Vp8Encoder
from docker_nvidia_glx_desktop_tpu.obs import content as obsc
from docker_nvidia_glx_desktop_tpu.ops import content_stats as cs

W, H = 64, 48


@pytest.fixture(autouse=True)
def _plane_on_after():
    """Every test leaves the master switch where the process default
    has it (ON) regardless of which arm it toggled last."""
    obsc.set_enabled(True)
    yield
    obsc.set_enabled(True)


def _frames(n, w=W, h=H, seed=3, step=2):
    r = np.random.default_rng(seed)
    base = r.integers(0, 256, size=(h, w, 3)).astype(np.uint8)
    base[h // 2: h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 220).astype(np.uint8)
    return [np.ascontiguousarray(np.roll(base, step * i, axis=1))
            for i in range(n)]


def _drive(enc, frames, stats_out=None):
    """The serving loop's pipelined shape; optionally pops the content
    stats after each collect (the web/session wiring)."""
    depth = getattr(enc, "pipeline_depth", 2)
    out, pend = [], []

    def collect():
        out.append(enc.encode_collect(pend.pop(0)))
        if stats_out is not None:
            stats_out.append(enc.pop_content_stats())

    for f in frames:
        pend.append(enc.encode_submit(f))
        while len(pend) >= depth:
            collect()
    while pend:
        collect()
    return out


def _assert_on_off_identical(make_enc, frames):
    """Same config, one instance per arm: ON bitstream == OFF
    bitstream, frame for frame."""
    obsc.set_enabled(True)
    stats = []
    ra = _drive(make_enc(), frames, stats_out=stats)
    obsc.set_enabled(False)
    rb = _drive(make_enc(), frames)
    obsc.set_enabled(True)
    assert len(ra) == len(rb) == len(frames)
    for i, (x, y) in enumerate(zip(ra, rb)):
        assert x.keyframe == y.keyframe, f"frame {i} keyframe mismatch"
        assert x.data == y.data, f"frame {i} AU diverges with stats on"
    return stats


class TestOnOffByteIdentity:
    def test_perframe_cavlc_gop_deep(self):
        frames = _frames(11)
        stats = _assert_on_off_identical(
            lambda: H264Encoder(W, H, entropy="device",
                                host_color=True, gop=5, deblock=True),
            frames)
        # the ON arm really measured: PSNR on every frame, damage from
        # the second ingest on, mode mix on the P frames
        assert all(s is not None for s in stats)
        assert all(s["psnr_db"] is not None for s in stats)
        assert all(s["damage_fraction"] is not None for s in stats[1:])
        p_stats = [s for s in stats if s["frame_type"] == "p"]
        assert p_stats and all(s["mode"] for s in p_stats)
        assert all(s["mode"]["intra"] == 1.0 for s in stats
                   if s["frame_type"] == "intra")

    def test_perframe_cabac_binarize_gop_deep(self):
        frames = _frames(9, seed=11)

        def make():
            e = H264Encoder(W, H, entropy="cabac",
                            host_color=True, gop=4, deblock=True)
            return e

        stats = _assert_on_off_identical(make, frames)
        assert all(s["psnr_db"] is not None for s in stats)

    def test_chunk_ring_gop_deep(self):
        frames = _frames(19, seed=7)
        stats = _assert_on_off_identical(
            lambda: H264Encoder(W, H, entropy="device",
                                host_color=True, gop=9, deblock=True,
                                superstep_chunk=4),
            frames)
        # chunked cadence: damage every frame, PSNR at chunk finals
        # (and on the IDRs, which ride the per-frame path)
        assert all(s["damage_fraction"] is not None for s in stats[1:])
        assert any(s["psnr_db"] is not None
                   and s["frame_type"] == "p" for s in stats)

    def test_spatial2_gop_deep(self):
        w, h = 64, 64
        frames = _frames(8, w=w, h=h, seed=5)
        stats = _assert_on_off_identical(
            lambda: H264Encoder(w, h, entropy="device",
                                host_color=True, gop=8, deblock=True,
                                spatial_shards=2),
            frames)
        # sharded frames still measure damage/activity (PSNR needs the
        # unsharded recon, which the spatial path does not stage)
        assert all(s is not None for s in stats)
        assert all(s["damage_fraction"] is not None for s in stats[1:])

    def test_vp8_on_off_identical(self):
        frames = _frames(7, seed=19)

        def run_arm(on):
            obsc.set_enabled(on)
            enc = Vp8Encoder(W, H, q_index=24, gop=4)
            outs, stats = [], []
            for f in frames:
                outs.append(enc.encode(f).data)
                stats.append(enc.pop_content_stats())
            return outs, stats

        on_out, on_stats = run_arm(True)
        off_out, off_stats = run_arm(False)
        obsc.set_enabled(True)
        assert on_out == off_out
        assert all(s is not None for s in on_stats)
        assert all(s is None for s in off_stats)
        assert all(s["psnr_db"] is not None for s in on_stats)


class TestInPathConsistency:
    def test_perframe_vs_chunked_stats_agree(self):
        """The per-frame and chunk stats programs are independent jit
        graphs fed by the same ingest chain: damage/mode/activity must
        agree frame-for-frame, PSNR (chunk finals) within 0.01 dB."""
        frames = _frames(19, seed=7)
        sa, sb = [], []
        _drive(H264Encoder(W, H, entropy="device",
                           host_color=True, gop=9, deblock=True),
               frames, stats_out=sa)
        _drive(H264Encoder(W, H, entropy="device",
                           host_color=True, gop=9, deblock=True,
                           superstep_chunk=4),
               frames, stats_out=sb)
        assert len(sa) == len(sb) == len(frames)
        compared_psnr = 0
        for i, (x, y) in enumerate(zip(sa, sb)):
            assert x["frame_type"] == y["frame_type"], i
            if x["damage_fraction"] is not None \
                    and y["damage_fraction"] is not None:
                assert x["damage_fraction"] == y["damage_fraction"], i
                np.testing.assert_array_equal(x["damage_grid"],
                                              y["damage_grid"])
            for k in ("act_p50", "act_p95"):
                np.testing.assert_allclose(x[k], y[k], rtol=1e-5,
                                           atol=1e-3)
            if x["mode"] and y["mode"] and y["frame_type"] == "p":
                for m in ("skip", "inter", "intra"):
                    assert x["mode"][m] == y["mode"][m], (i, m)
            if x["psnr_db"] is not None and y["psnr_db"] is not None:
                assert abs(x["psnr_db"] - y["psnr_db"]) < 0.01, i
                compared_psnr += 1
        assert compared_psnr >= 3       # IDRs + chunk finals

    def test_device_stats_match_oracle_in_path(self):
        """Damage measured INSIDE the real encode path must equal the
        numpy oracle applied to the same ingest planes."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import _yuv_stage

        frames = _frames(5, seed=23)
        enc = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=5, deblock=True)
        stats = []
        _drive(enc, frames, stats_out=stats)
        thr = obsc.damage_thr_sad()
        ys = [np.asarray(_yuv_stage(np.asarray(f), enc.pad_h,
                                    enc.pad_w)[0])
              for f in frames]
        npix = enc.pad_h * enc.pad_w
        for i in range(1, len(frames)):
            vec, grid = cs.frame_stats_np(ys[i], ys[i - 1],
                                          thr_sad=thr)
            want = cs.vec_to_stats(vec, grid, npix)
            assert stats[i]["damage_fraction"] == \
                want["damage_fraction"], i
            np.testing.assert_array_equal(stats[i]["damage_grid"],
                                          want["damage_grid"])
            # activity is a float32 variance sum (~1e8): device
            # accumulation order differs from the float64 oracle
            np.testing.assert_allclose(stats[i]["act_p50"],
                                       want["act_p50"], rtol=1e-3)

    def test_calm_desktop_less_damage_than_noise(self):
        """The plane's defining measurement: a mostly-static desktop
        (tiny cursor-sized delta per frame) must score strictly less
        damage than full-frame noise."""
        r = np.random.default_rng(0)
        base = r.integers(0, 256, size=(H, W, 3)).astype(np.uint8)
        calm = []
        for i in range(6):
            f = base.copy()
            f[4:12, 4 + i:12 + i] = 255          # a moving "cursor"
            calm.append(f)
        noise = [r.integers(0, 256, size=(H, W, 3)).astype(np.uint8)
                 for _ in range(6)]

        def mean_damage(frames):
            enc = H264Encoder(W, H, entropy="device",
                              host_color=True, gop=6, deblock=True)
            stats = []
            _drive(enc, frames, stats_out=stats)
            vals = [s["damage_fraction"] for s in stats
                    if s and s["damage_fraction"] is not None]
            assert vals
            return float(np.mean(vals))

        calm_damage = mean_damage(calm)
        noise_damage = mean_damage(noise)
        assert calm_damage < noise_damage
        assert noise_damage > 0.9        # noise slams every MB
        assert calm_damage < 0.2         # the cursor touches a few
