"""Single-session spatial mesh sharding (ISSUE 12 tentpole): ONE
frame's MB rows across N chips must be BYTE-IDENTICAL to the
single-device path GOP-deep — CAVLC and CABAC-device-binarize, deblock
on and off, on (1, N) meshes with N in {2, 4} — through the REAL
serving encoder (submit/collect pipeline and the GOP-chunk super-step
ring), not just the raw kernels.  Plus the CABAC record-stream row
stitch oracle, the shard-count planning arithmetic, and the retrace
tripwire for the sharded chunk step.
"""

import numpy as np
import pytest

import conftest  # noqa: F401  (forces the 8-device CPU backend)
import jax

from docker_nvidia_glx_desktop_tpu.models.h264 import (
    H264Encoder, spatial_auto_shards)
from docker_nvidia_glx_desktop_tpu.parallel import batch

assert len(jax.devices()) >= 8, (
    "conftest.py failed to force 8 CPU devices — spatial-shard tests "
    "would silently run unsharded")

W, H = 64, 64        # 4 MB rows: nx=2 leaves 2 rows/shard (halo ok)
W4, H4 = 64, 128     # 8 MB rows: nx=4 leaves 2 rows/shard


def _frames(n, w=W, h=H, seed=3, step=2):
    r = np.random.default_rng(seed)
    base = r.integers(0, 256, size=(h, w, 3)).astype(np.uint8)
    base[h // 2: h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 220).astype(np.uint8)
    return [np.ascontiguousarray(np.roll(np.roll(base, step * i, axis=1),
                                         (step * i) % 5, axis=0))
            for i in range(n)]


def _drive(enc, frames):
    """The serving loop's pipelined shape at the encoder's preferred
    depth; returns the EncodedFrames in order."""
    depth = getattr(enc, "pipeline_depth", 2)
    out, pend = [], []
    for f in frames:
        pend.append(enc.encode_submit(f))
        while len(pend) >= depth:
            out.append(enc.encode_collect(pend.pop(0)))
    while pend:
        out.append(enc.encode_collect(pend.pop(0)))
    return out


def _assert_streams_equal(single, spatial, frames):
    ra, rb = _drive(single, frames), _drive(spatial, frames)
    assert len(ra) == len(rb) == len(frames)
    for i, (x, y) in enumerate(zip(ra, rb)):
        assert x.keyframe == y.keyframe, f"frame {i} keyframe mismatch"
        assert x.data == y.data, f"frame {i} AU diverges"


class TestSpatialByteIdentity:
    """Acceptance bar: sharded AUs byte-identical to single-device
    GOP-deep, CAVLC + CABAC-binarize, deblock on/off, N in {2, 4}."""

    @pytest.mark.parametrize("nx,w,h,deblock", [
        (2, W, H, True),
        (2, W, H, False),
        (4, W4, H4, True),
        (4, W4, H4, False),
    ])
    def test_cavlc_gop_deep(self, nx, w, h, deblock):
        frames = _frames(8, w=w, h=h, seed=5 + nx)
        kw = dict(entropy="device", host_color=True,
                  gop=8, deblock=deblock)
        a = H264Encoder(w, h, **kw)
        b = H264Encoder(w, h, spatial_shards=nx, **kw)
        assert b._spatial_nx == nx
        _assert_streams_equal(a, b, frames)

    @pytest.mark.parametrize("nx,w,h,deblock", [
        (2, W, H, True),
        (2, W, H, False),
        (4, W4, H4, True),
    ])
    def test_cabac_binarize_gop_deep(self, nx, w, h, deblock):
        frames = _frames(7, w=w, h=h, seed=11 + nx)
        kw = dict(entropy="cabac", host_color=True,
                  gop=7, deblock=deblock)
        a = H264Encoder(w, h, **kw)
        b = H264Encoder(w, h, spatial_shards=nx, **kw)
        assert b._spatial_nx == nx
        _assert_streams_equal(a, b, frames)

    def test_all_intra_spatial(self):
        """gop=1 (all-intra) shards too — every frame an IDR, no
        reference ring."""
        frames = _frames(4, seed=17)
        kw = dict(entropy="device", host_color=True)
        a = H264Encoder(W, H, **kw)
        b = H264Encoder(W, H, spatial_shards=2, **kw)
        _assert_streams_equal(a, b, frames)

    def test_spatial_chunk_ring_byte_identical(self):
        """The sharded GOP-chunk super-step (devloop.build_p_chunk_step
        grown the spatial axis): staged frames, one donated-ring
        dispatch per chunk, byte-identical to the plain single-device
        per-frame path — and ~1 crossing per chunk."""
        frames = _frames(13, seed=13, step=3)
        a = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=13, deblock=True)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=13, deblock=True,
                        spatial_shards=2, superstep_chunk=4)
        assert b._ring_chunk == 4 and b._spatial_nx == 2
        _assert_streams_equal(a, b, frames)
        # 13 frames = 1 IDR + 12 P = 1 + 3 chunk dispatches
        assert b._disp_count == 1 + 3

    def test_spatial_cabac_chunk_ring(self):
        frames = _frames(10, seed=19, step=3)
        kw = dict(entropy="cabac", host_color=True,
                  gop=10, deblock=True)
        a = H264Encoder(W, H, **kw)
        b = H264Encoder(W, H, spatial_shards=2, superstep_chunk=3,
                        **kw)
        assert b._ring_chunk == 3
        _assert_streams_equal(a, b, frames)

    def test_spatial_checkpoint_roundtrip(self):
        """export_state gathers the sharded ring to host; import onto a
        fresh spatial encoder resumes with a recovery IDR (continuity
        contract unchanged under sharding)."""
        frames = _frames(6, seed=23)
        src = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=12, deblock=True,
                          spatial_shards=2)
        for f in frames[:4]:
            src.encode(f)
        st = src.export_state()
        assert st["ref"] is not None
        dst = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=12, deblock=True,
                          spatial_shards=2)
        dst.import_state(st)
        out = [dst.encode(f) for f in frames[4:]]
        assert out[0].keyframe          # recovery IDR
        assert all(len(o.data) > 0 for o in out)


class TestManagerSpatialPlan:
    def test_manager_plans_and_serves_spatial_mesh(self):
        """ENCODER_SPATIAL_SHARDS turns the batch manager's mesh plan
        into (1 session x N spatial) via replan_mesh, and the sharded
        bucket actually encodes a GOP (IDR + P over the halo path)."""
        from docker_nvidia_glx_desktop_tpu.rfb.source import (
            SyntheticSource)
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env
        from docker_nvidia_glx_desktop_tpu.web.multisession import (
            BatchStreamManager)

        cfg = from_env({"SIZEW": "64", "SIZEH": "128",
                        "ENCODER_GOP": "4",
                        "ENCODER_SPATIAL_SHARDS": "4",
                        "WEBRTC_ENCODER": "tpuh264enc"})
        src = SyntheticSource(64, 128)
        mgr = BatchStreamManager(cfg, [src])
        try:
            assert tuple(mgr.mesh.devices.shape) == (1, 4)
            for tick in range(3):
                frame = src.frame()[0]
                y, cb, cr = mgr._planes(frame, 0)
                results = mgr._encode_tick(y[None], cb[None], cr[None])
                # (flat, idr, jmeta) since the PR 13 journey plumbing
                for flat, idr, _jmeta in results:
                    assert idr == (tick == 0)
                    au = mgr._batch.assemble_session_h264(
                        flat[0], mgr.rows_local,
                        headers=mgr._hub_headers[0] if idr else b"")
                    assert len(au) > 0
        finally:
            mgr.close()

    def test_knob_off_or_explicit_mesh_wins(self):
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env
        from docker_nvidia_glx_desktop_tpu.rfb.source import (
            SyntheticSource)
        from docker_nvidia_glx_desktop_tpu.web.multisession import (
            BatchStreamManager)

        cfg = from_env({"SIZEW": "64", "SIZEH": "128",
                        "WEBRTC_ENCODER": "tpuh264enc"})
        mgr = BatchStreamManager(cfg, [SyntheticSource(64, 128)])
        try:
            assert tuple(mgr.mesh.devices.shape) == (1, 1)
        finally:
            mgr.close()


class TestStitchOracle:
    def test_stitch_rows_matches_whole_frame_binarize(self):
        """binarize_p of each half-frame row block, stitched, must
        carry exactly the whole-frame buffer's per-row payloads (the
        per-row independence claim the CABAC spatial path rests on)."""
        from docker_nvidia_glx_desktop_tpu.ops import (cabac_binarize,
                                                       h264_inter)

        r = np.random.default_rng(7)
        h, w = 64, 64
        y = r.integers(0, 256, (h, w)).astype(np.uint8)
        cb = r.integers(0, 256, (h // 2, w // 2)).astype(np.uint8)
        cr = r.integers(0, 256, (h // 2, w // 2)).astype(np.uint8)
        ry = np.roll(y, 2, axis=1)
        rcb = np.roll(cb, 1, axis=1)
        rcr = np.roll(cr, 1, axis=1)
        out = h264_inter.encode_p_frame(y, cb, cr, ry, rcb, rcr, qp=28)
        lv = {k: np.asarray(out[k]) for k in
              ("mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")}
        whole = np.asarray(cabac_binarize.binarize_p(
            lv["mv"], lv["luma"], lv["cb_dc"], lv["cb_ac"],
            lv["cr_dc"], lv["cr_ac"]))
        nr = h // 16
        half = nr // 2
        parts = []
        for sl in (slice(0, half), slice(half, nr)):
            parts.append(np.asarray(cabac_binarize.binarize_p(
                lv["mv"][sl], lv["luma"][sl], lv["cb_dc"][sl],
                lv["cb_ac"][sl], lv["cr_dc"][sl], lv["cr_ac"][sl])))
        stitched = cabac_binarize.stitch_rows(parts, half)
        sw = cabac_binarize.split_rows(whole, nr)
        ss = cabac_binarize.split_rows(stitched, nr)
        assert sw is not None and ss is not None
        np.testing.assert_array_equal(sw[1], ss[1])   # row offsets
        np.testing.assert_array_equal(sw[2], ss[2])   # row bit counts
        np.testing.assert_array_equal(sw[0], ss[0])   # payload words

    def test_stitch_overflow_poisons_header(self):
        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

        good = np.zeros(cabac_binarize.META_WORDS + 2, np.uint32)
        good[0], good[3] = 2, 2
        bad = good.copy()
        bad[1] = 1
        out = cabac_binarize.stitch_rows([good, bad], 2)
        assert int(out[1]) == 1
        assert cabac_binarize.split_rows(out, 4) is None


class TestShardPlanning:
    def test_feasible_spatial_shards(self):
        f = batch.feasible_spatial_shards
        # the coded height follows the mesh (PR 36): 4K native is 135
        # MB rows, coded as 136 over 2 or 4 chips (68 / 34 a shard)
        assert f(2160, 2, 8) == 2
        assert f(2160, 4, 8) == 4
        assert f(2160, 1, 8) == 1
        assert batch.coded_height(2160, 4) == 2176
        assert batch.coded_height(2160, 2) == 2176
        assert batch.coded_height(2160, 1) == 2160
        # 2176 (136 rows) splits 2/4/8 as it is, and 3-way with two
        # rows of padding (138 rows, 46 a shard)
        assert f(2176, 4, 8) == 4
        assert f(2176, 3, 8) == 3
        assert batch.coded_height(2176, 3) == 2208
        # halo infeasibility: 4 rows cannot split 4 ways (1 row/shard
        # donates too little chroma halo), nor 3 ways (6 coded rows
        # would leave the third shard padding alone)
        assert f(64, 4, 8) == 2
        # device ceiling
        assert f(2176, 4, 2) == 2

    def test_spatial_auto_shards_uses_slo_budget(self):
        class FakeModel:
            def chips_for_session(self, w, h, fps, max_chips=8,
                                  budget_ms=None):
                self.seen = (w, h, fps, max_chips, budget_ms)
                return 4

        m = FakeModel()
        n = spatial_auto_shards(3840, 2160, 30.0, n_devices=8, model=m)
        assert n == 4
        # the 4k30 SLO rung's 33.3 ms budget, not a bare frame interval
        assert m.seen[4] == pytest.approx(33.3)

    def test_encoder_resolution_clamps(self):
        # 64x64 = 4 rows: a request for 4 shards clamps to 2 (halo)
        enc = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=4, spatial_shards=4)
        assert enc._spatial_nx == 2
        # keep_recon (the PSNR hook) disables sharding
        enc2 = H264Encoder(W, H, entropy="device",
                           host_color=True, gop=4, keep_recon=True,
                           spatial_shards=2)
        assert enc2._spatial_nx == 1
        # host-entropy modes never shard
        enc3 = H264Encoder(W, H, entropy="python",
                           gop=4, spatial_shards=2)
        assert enc3._spatial_nx == 1


@pytest.mark.slow
class TestSpatialRetrace:
    """ISSUE 12 satellite: the sharded chunk step is compile-silent
    over 2 steady GOP-chunks after warm-up, and a shard-count change
    costs exactly one recompile (mirrors tests/test_superstep.py)."""

    def _chunk_inputs(self, w, h, k, seed=3):
        from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

        r = np.random.default_rng(seed)
        y0 = r.integers(0, 256, (h, w)).astype(np.uint8)
        cb0 = r.integers(0, 256, (h // 2, w // 2)).astype(np.uint8)
        cr0 = r.integers(0, 256, (h // 2, w // 2)).astype(np.uint8)
        ys = np.stack([np.roll(y0, 2 * (i + 1), axis=1)
                       for i in range(k)])
        cbs = np.stack([np.roll(cb0, i + 1, axis=1) for i in range(k)])
        crs = np.stack([np.roll(cr0, i + 1, axis=1) for i in range(k)])
        hvs, hls = [], []
        for fn in range(1, k + 1):
            hv, hl = cavlc_device.slice_header_slots(
                h // 16, w // 16, frame_num=fn, slice_type=5,
                idr=False, deblocking_idc=2)
            hvs.append(np.asarray(hv))
            hls.append(np.asarray(hl))
        # refs stay HOST arrays: a device-0-committed ref would compile
        # separate resharding programs on its way to P("spatial"),
        # polluting the one-compile count this class pins
        refs = (y0, cb0, cr0)
        return (ys, cbs, crs), refs, (np.stack(hvs), np.stack(hls))

    def test_steady_state_silent_and_shard_change_one_compile(self):
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)
        from docker_nvidia_glx_desktop_tpu.ops import devloop

        if not compile_events_supported():
            pytest.skip("jax.monitoring compile events unavailable")
        k = 3
        step2 = devloop.build_p_chunk_step(
            26, deblock=True, entropy="cavlc", ingest="yuv",
            prefix_len=0, spatial_shards=2)
        frames, refs, hdrs = self._chunk_inputs(W, H, k)
        # 2 warm-up chunks: first compiles, second proves the donated
        # sharded ring re-enters the same executable unrepartitioned
        for _ in range(2):
            out = step2(*frames, *refs, *hdrs)
            np.asarray(out[0])
            refs = (out[2], out[3], out[4])
        with RetraceTripwire(label="steady-state spatial chunk") as tw:
            for _ in range(2):
                out = step2(*frames, *refs, *hdrs)
                np.asarray(out[0])
                refs = (out[2], out[3], out[4])
        tw.assert_quiet()
        # shard-count change: a NEW mesh shape = exactly ONE compile
        step4 = devloop.build_p_chunk_step(
            26, deblock=True, entropy="cavlc", ingest="yuv",
            prefix_len=0, spatial_shards=4)
        frames4, refs4, hdrs4 = self._chunk_inputs(W4, H4, k, seed=9)
        with RetraceTripwire(label="shard-count change") as tw2:
            out = step4(*frames4, *refs4, *hdrs4)
            np.asarray(out[0])
        assert tw2.compiles == 1, tw2.sites
