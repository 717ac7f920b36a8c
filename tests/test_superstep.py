"""GOP-chunk super-step (ROADMAP item 2): donated ring-buffer chunk
dispatch must be BYTE-IDENTICAL to the per-frame path on every codec
path (device CAVLC, CABAC device-binarize, deblock on/off, I16/I_NxN
IDRs), single-device and mesh-sharded — and compile-silent in steady
state (the PR 7 retrace tripwire proves the "persistent compiled
serving graph" claim, not just the speedup).
"""

import numpy as np
import pytest

import conftest  # noqa: F401  (forces the 8-device CPU backend)
from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

W, H = 64, 48


def _frames(n, w=W, h=H, seed=3, step=2):
    r = np.random.default_rng(seed)
    base = r.integers(0, 256, size=(h, w, 3)).astype(np.uint8)
    # mix rolls with a noise band so chroma/luma residuals stay rich
    base[h // 2: h // 2 + h // 8] = (
        r.integers(0, 2, size=(h // 8, w, 3)) * 220).astype(np.uint8)
    return [np.ascontiguousarray(np.roll(base, step * i, axis=1))
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


def _assert_streams_equal(a, b, frames):
    ra, rb = _drive(a, frames), _drive(b, frames)
    assert len(ra) == len(rb) == len(frames)
    for i, (x, y) in enumerate(zip(ra, rb)):
        assert x.keyframe == y.keyframe, f"frame {i} keyframe mismatch"
        assert x.data == y.data, f"frame {i} AU diverges"
    return ra, rb


class TestRingByteIdentity:
    def test_cavlc_deblock_gop_deep(self):
        """2+ GOPs (gop=9, chunk=4: each P-run is exactly 2 chunks)
        through the ring vs per-frame — plus the crossings claim: the
        ring must dispatch ~once per chunk, not per frame."""
        frames = _frames(19)
        a = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=9, deblock=True)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=9, deblock=True,
                        superstep_chunk=4)
        assert b._ring_chunk == 4 and b.pipeline_depth == 5
        _assert_streams_equal(a, b, frames)
        # 19 frames = 3 IDRs + 16 P = 3 + 4 chunk dispatches; the
        # per-frame twin crosses once per frame
        assert a._disp_count == 19
        assert b._disp_count == 3 + 4

    def test_cavlc_partial_chunk_flush_at_idr(self):
        """gop=8 with chunk=3: every P-run is 2 chunks + 1 flushed
        frame — the IDR-due flush must be byte-invisible."""
        frames = _frames(17, seed=5)
        a = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=8, deblock=True)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=8, deblock=True,
                        superstep_chunk=3)
        _assert_streams_equal(a, b, frames)

    def test_cavlc_no_deblock_inxn_intra(self):
        """deblock off + nine-mode I_NxN IDRs: the ring's recon chain
        (refs aliased in place, no loop filter) must still match."""
        frames = _frames(10, seed=7)
        kw = dict(entropy="device", host_color=True,
                  gop=10, deblock=False, intra_modes="full")
        a = H264Encoder(W, H, **kw)
        b = H264Encoder(W, H, superstep_chunk=3, **kw)
        _assert_streams_equal(a, b, frames)

    def test_cabac_device_binarize(self):
        """CABAC path: the chunk step fuses binarize_p into the scan;
        the host engine replays per frame — byte-identical streams."""
        frames = _frames(8, w=48, h=32, seed=9)
        kw = dict(entropy="cabac", host_color=True,
                  gop=8, deblock=True)
        a = H264Encoder(48, 32, **kw)
        b = H264Encoder(48, 32, superstep_chunk=3, **kw)
        assert b._ring_chunk == 3
        _assert_streams_equal(a, b, frames)

    def test_drain_flushes_partial_ring(self):
        """A collect reaching a frame whose chunk never filled (idle
        source / pipeline drain) must flush per-frame, byte-identically
        — frames are never stranded in the ring."""
        frames = _frames(6, seed=11)            # gop=16: IDR + 5 staged P
        a = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=16, deblock=True)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=16, deblock=True,
                        superstep_chunk=4)
        ra = [a.encode_collect(a.encode_submit(f)) for f in frames]
        # submit everything, then drain: frame 5 sits in a 1-deep ring
        pend = [b.encode_submit(f) for f in frames]
        rb = [b.encode_collect(t) for t in pend]
        for i, (x, y) in enumerate(zip(ra, rb)):
            assert x.data == y.data, f"frame {i} diverges on drain"

    def test_rate_controlled_ring_reservations(self):
        """The ring freezes qp per chunk (qp is a static jit arg — a
        DOCUMENTED semantic difference from per-frame qp movement), but
        the rate controller's per-frame reservation/update ledger must
        stay exactly aligned: one reservation per staged frame, one pop
        per collected frame, P sizes never mis-attributed to the
        keyframe EMA."""
        from docker_nvidia_glx_desktop_tpu.models.h264 import (
            RateController)

        # unit-level: repeat_last_reservation duplicates type AND step
        rc = RateController(26, 800, 30.0)
        rc.qp_for(True)
        rc.update(40000)                    # keyframe sample
        kf_ema = rc._ema[True]
        rc.qp_for(False)
        for _ in range(3):
            rc.repeat_last_reservation()
        assert rc.pending_count == 4
        for _ in range(4):
            rc.update(5000)                 # four P pops, P attribution
        assert rc.pending_count == 0
        assert rc._ema[False] is not None
        assert rc._ema[True] == kf_ema      # P updates never touched it

        # integration: a rate-controlled ring run drains its ledger
        frames = _frames(13, seed=13)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=13, deblock=True,
                        bitrate_kbps=800, fps=30.0, superstep_chunk=4)
        assert b._ring_chunk == 4
        out = _drive(b, frames)
        assert len(out) == 13 and out[0].keyframe
        assert all(len(f.data) > 0 for f in out)
        assert b._rate.pending_count == 0   # no orphaned reservations


class TestRingOverflowFallback:
    def test_overflow_falls_back_to_host_entropy_of_chunk_levels(self):
        """Force the flat-cap overflow flag on one chunk slot and prove
        the ring collect host-entropy-codes the chunk's own level
        tensors (no access to the consumed refs) — byte-identical to
        the per-frame stream."""
        frames = _frames(6, seed=17)
        b = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=16, deblock=True,
                        superstep_chunk=4)
        pend = [b.encode_submit(f) for f in frames[:5]]
        ring, slot = pend[-1][4]
        assert ring["res"] is not None      # chunk dispatched at K=4
        # flip the overflow flag (flat meta word 0, big-endian: byte 3
        # is the LSB) for slot 1 only — collect must take the dense
        # host-entropy path for that frame and the fast path for the
        # rest
        prefix = np.asarray(ring["res"][1]).copy()
        prefix[1][3] = 1
        ring["prefix_np"] = prefix
        # per-frame twin for the expected bytes
        a = H264Encoder(W, H, entropy="device",
                        host_color=True, gop=16, deblock=True)
        want = [a.encode_collect(a.encode_submit(f))
                for f in frames[:5]]
        got = [b.encode_collect(t) for t in pend]
        for i, (x, y) in enumerate(zip(want, got)):
            assert x.data == y.data, f"frame {i} diverges via fallback"


class TestDonatedRing:
    def test_refs_are_consumed_by_the_p_stage(self):
        """The donation contract is real: passing a ref ring to the P
        stage invalidates the caller's handles (XLA aliased them into
        the new recon) — the analysis jax-donate-missing fix is not
        cosmetic."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import cavlc_p_device
        from docker_nvidia_glx_desktop_tpu.ops import cavlc_device
        from docker_nvidia_glx_desktop_tpu.ops.h264_inter import (
            RING_DONATE)

        if not RING_DONATE:
            pytest.skip("ring donation resolved off on this backend "
                        "(ops/h264_inter.ring_donate_argnames)")
        r = np.random.default_rng(1)
        y = jnp.asarray(r.integers(0, 256, (H, W)).astype(np.uint8))
        cb = jnp.asarray(r.integers(0, 256, (H // 2, W // 2)
                                    ).astype(np.uint8))
        cr = jnp.asarray(r.integers(0, 256, (H // 2, W // 2)
                                    ).astype(np.uint8))
        ry, rcb, rcr = (jnp.array(y), jnp.array(cb), jnp.array(cr))
        hv, hl = cavlc_device.slice_header_slots(
            H // 16, W // 16, frame_num=1, slice_type=5, idr=False)
        out = cavlc_p_device.encode_p_cavlc_frame(
            y, cb, cr, ry, rcb, rcr, jnp.asarray(hv), jnp.asarray(hl),
            26)
        np.asarray(out[0])                  # force execution
        with pytest.raises(RuntimeError):
            np.asarray(ry)                  # donated: handle is dead


@pytest.mark.slow
class TestRetraceTripwire:
    """ISSUE 8 satellite: 2 warm-up chunks, then 2 steady-state chunks
    compile-silent; a geometry re-bucket triggers exactly ONE fresh
    compile of the chunk step."""

    def _chunk_inputs(self, w, h, k, seed=3):
        import jax.numpy as jnp

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
                h // 16, w // 16, frame_num=fn, slice_type=5, idr=False,
                deblocking_idc=2)
            hvs.append(np.asarray(hv))
            hls.append(np.asarray(hl))
        refs = tuple(jnp.asarray(p) for p in (y0, cb0, cr0))
        return (ys, cbs, crs), refs, (np.stack(hvs), np.stack(hls))

    def test_steady_state_compile_silent_then_one_rebucket_compile(self):
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)
        from docker_nvidia_glx_desktop_tpu.ops import devloop

        if not compile_events_supported():
            pytest.skip("jax.monitoring compile events unavailable")
        step = devloop.build_p_chunk_step(26, deblock=True,
                                          entropy="cavlc", ingest="yuv",
                                          prefix_len=0)
        k = 3
        frames, refs, hdrs = self._chunk_inputs(W, H, k)
        # 2 warm-up chunks (first compiles, second proves the donated
        # ring re-enters the same executable)
        for _ in range(2):
            out = step(*frames, *refs, *hdrs)
            np.asarray(out[0])
            refs = (out[2], out[3], out[4])
        with RetraceTripwire(label="steady-state super-step") as tw:
            for _ in range(2):
                out = step(*frames, *refs, *hdrs)
                np.asarray(out[0])
                refs = (out[2], out[3], out[4])
        tw.assert_quiet()
        # geometry re-bucket: one (and only one) fresh compile
        frames2, refs2, hdrs2 = self._chunk_inputs(W + 16, H + 16, k)
        with RetraceTripwire(label="geometry re-bucket") as tw2:
            out = step(*frames2, *refs2, *hdrs2)
            np.asarray(out[0])
        assert tw2.compiles == 1, tw2.sites

    def test_serving_ring_compile_silent(self):
        """The whole encoder ring (intra + chunk + pulls): after 2
        warm-up chunks the next 2 chunks' worth of frames must not
        compile anything."""
        from docker_nvidia_glx_desktop_tpu.analysis.retrace import (
            RetraceTripwire, compile_events_supported)

        if not compile_events_supported():
            pytest.skip("jax.monitoring compile events unavailable")
        frames = _frames(25, seed=19)
        enc = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=25, deblock=True,
                          superstep_chunk=4)
        pend = []
        for f in frames[:17]:               # IDR + 4 chunks warm-up
            pend.append(enc.encode_submit(f))
            while len(pend) >= enc.pipeline_depth:
                enc.encode_collect(pend.pop(0))
        with RetraceTripwire(label="steady-state serving ring") as tw:
            for f in frames[17:]:           # 2 more whole chunks
                pend.append(enc.encode_submit(f))
                while len(pend) >= enc.pipeline_depth:
                    enc.encode_collect(pend.pop(0))
        tw.assert_quiet()
        while pend:
            enc.encode_collect(pend.pop(0))


class TestMeshChunkStep:
    def test_mesh_chunk_byte_identical_and_ring_seeded(self):
        """(n/2, 2) mesh: the chunk step's scan (halo exchange +
        sharded deblock inside the body) must match chunk consecutive
        per-frame batch steps byte-for-byte, and return the reference
        ring under the same sharding it consumed."""
        import jax
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import cavlc_device
        from docker_nvidia_glx_desktop_tpu.parallel import batch

        if len(jax.devices()) < 4:
            pytest.skip("needs 4 forced host devices")
        ns, nx = 2, 2
        h, w, qp, k = 96, 64, 30, 3
        mesh = batch.make_mesh((ns, nx), jax.devices()[:4])
        assert batch.p_halo_feasible(h, nx)
        r = np.random.default_rng(5)
        ys0 = r.integers(0, 256, (ns, h, w)).astype(np.uint8)
        cbs0 = r.integers(0, 256, (ns, h // 2, w // 2)).astype(np.uint8)
        crs0 = r.integers(0, 256, (ns, h // 2, w // 2)).astype(np.uint8)

        def hdr(fn):
            hv, hl = cavlc_device.slice_header_slots(
                h // 16, w // 16, frame_num=fn, slice_type=5, idr=False)
            return np.asarray(hv), np.asarray(hl)

        frames = [tuple(np.ascontiguousarray(np.roll(p, 2 * (i + 1),
                                                     axis=2))
                        for p in (ys0, cbs0, crs0)) for i in range(k)]
        p_step, rows_l = batch.h264_p_batch_step(mesh, h, w, qp=qp,
                                                 deblock=True)
        ref = (ys0, cbs0, crs0)
        per = []
        for i in range(k):
            hv, hl = hdr(i + 1)
            flat, *ref = p_step(*frames[i], *ref, hv, hl)
            per.append(np.asarray(flat))

        c_step, rows_c = batch.h264_p_chunk_batch_step(
            mesh, h, w, k, qp=qp, deblock=True)
        assert rows_c == rows_l
        ys = np.stack([f[0] for f in frames], axis=1)
        cbs = np.stack([f[1] for f in frames], axis=1)
        crs = np.stack([f[2] for f in frames], axis=1)
        hvs = np.stack([hdr(i + 1)[0] for i in range(k)])
        hls = np.stack([hdr(i + 1)[1] for i in range(k)])
        flats, nry, nrcb, nrcr = c_step(
            ys, cbs, crs, jnp.asarray(ys0), jnp.asarray(cbs0),
            jnp.asarray(crs0), hvs, hls)
        flats = np.asarray(flats)
        for i in range(k):
            assert (flats[:, i] == per[i]).all(), f"frame {i} diverges"
        # the ring comes back equal to the per-frame chain's refs and
        # re-enters the next chunk without repartitioning
        assert (np.asarray(nry) == np.asarray(ref[0])).all()
        flats2, *_ = c_step(ys, cbs, crs, nry, nrcb, nrcr, hvs, hls)
        assert np.asarray(flats2).shape == flats.shape

    def test_manager_chunk_mode_smoke(self):
        """BatchStreamManager drives the super-step: staged ticks emit
        nothing, the chunk tick emits K AUs, an IDR-due partial stage
        flushes — GOP accounting intact."""
        from docker_nvidia_glx_desktop_tpu.rfb.source import (
            SyntheticSource)
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env
        from docker_nvidia_glx_desktop_tpu.web.multisession import (
            BatchStreamManager)

        cfg = from_env({"SIZEW": "64", "SIZEH": "48", "ENCODER_GOP": "6",
                        "ENCODER_SUPERSTEP_CHUNK": "3",
                        "WEBRTC_ENCODER": "tpuh264enc"})
        sources = [SyntheticSource(64, 48), SyntheticSource(64, 48)]
        mgr = BatchStreamManager(cfg, sources)
        assert mgr.chunk == 3 and mgr.chunk_step is not None
        try:
            def tick():
                frames = [s.frame()[0] for s in sources]
                planes = [mgr._planes(f, i)
                          for i, f in enumerate(frames)]
                ys = np.stack([p[0] for p in planes])
                cbs = np.stack([p[1] for p in planes])
                crs = np.stack([p[2] for p in planes])
                return mgr._encode_tick(ys, cbs, crs)

            emitted = []
            for _ in range(14):              # 2+ GOPs of 6
                emitted.append(tick())
            sizes = [len(e) for e in emitted]
            # GOP of 6 under chunk 3: IDR(1), stage, stage, chunk(3),
            # stage, stage, [IDR due -> flush(2) + IDR(1)] ...
            assert sizes[:7] == [1, 0, 0, 3, 0, 0, 3], sizes
            assert emitted[0][0][1] is True
            kinds = [[idr for _, idr, _ in e] for e in emitted]
            assert kinds[3] == [False, False, False]
            assert kinds[6] == [False, False, True]   # flush + IDR
            # every emitted AU assembles and is non-empty
            for e in emitted:
                for flat, idr, _jmeta in e:
                    au = mgr._batch.assemble_session_h264(
                        flat[0], mgr.rows_local,
                        headers=mgr._hub_headers[0] if idr else b"")
                    assert len(au) > 0
        finally:
            mgr.close()
