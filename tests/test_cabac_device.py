"""Round-6 tentpole coverage: device-side CABAC binarization + ctxIdx
(ops/cabac_binarize -> engine-only host replay), alternate-line subpel
SAD pick agreement, and the loop filter's kernel against its scan.

Byte-identity is the acceptance bar throughout: the record stream must
drive the arithmetic engine through EXACTLY the decision sequence the
reference coder makes, and the restructured deblock/ME paths must leave
every conformance contract intact.
"""

import numpy as np
import pytest

import conftest


def _yuv(rgb, w, h):
    from docker_nvidia_glx_desktop_tpu.utils.hostcolor import (
        rgb_to_yuv420_host)
    return rgb_to_yuv420_host(rgb, h, w, float_fallback=True)


def _p_levels(qp=26, seed=9, w=128, h=96, step=4):
    """Realistic P-frame level tensors via the actual inter stage."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import h264_inter

    base = conftest.make_test_frame(h, w, seed=seed)
    f0 = _yuv(base, w, h)
    f1 = _yuv(np.ascontiguousarray(np.roll(base, step, axis=1)), w, h)
    return h264_inter.encode_p_frame(
        *[jnp.asarray(p) for p in f1], *[jnp.asarray(p) for p in f0],
        qp=qp)


class TestRecordStream:
    def test_wire_format_parses_exactly(self):
        """Every row's record stream must parse to its exact bit count
        (a mis-sized record would desync the engine silently)."""
        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

        out = _p_levels()
        buf = np.asarray(cabac_binarize.binarize_p(
            out["mv"], out["luma"], out["cb_dc"], out["cb_ac"],
            out["cr_dc"], out["cr_ac"]))
        split = cabac_binarize.split_rows(buf, 96 // 16)
        assert split is not None, "unexpected overflow flag"
        payload, row_off, row_bits = split
        n_recs = 0
        for r in range(96 // 16):
            recs = cabac_binarize.decode_records_py(
                payload[row_off[r]:row_off[r + 1]], int(row_bits[r]))
            n_recs += len(recs)
            assert recs[-1][0] == "trm" and recs[-1][1] == 1
        assert n_recs > 0

    @pytest.mark.parametrize("idc", [0, 1, 2])
    def test_p_byte_identical_to_reference_coder(self, idc):
        """Device binarize -> engine replay must equal the host CABAC
        coder byte-for-byte (slice payloads AND NAL framing)."""
        from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

        out = _p_levels(qp=26)
        dense = {k: np.asarray(out[k], np.int32)
                 for k in ("mv", "luma", "cb_dc", "cb_ac", "cr_dc",
                           "cr_ac")}
        want = h264_cabac.encode_p_picture(
            dense, qp=26, frame_num=1, cabac_init_idc=idc)
        buf = np.asarray(cabac_binarize.binarize_p(
            out["mv"], out["luma"], out["cb_dc"], out["cb_ac"],
            out["cr_dc"], out["cr_ac"]))
        got = h264_cabac.encode_p_from_binstream(
            buf, nr=6, nc_mb=8, qp=26, frame_num=1, cabac_init_idc=idc)
        assert got is not None
        assert got == want

    def test_p_skip_runs_and_extreme_levels(self):
        """Crafted corner mix: all-skip rows, a lone max-suffix level
        (UEG0 escape), negative levels, and large mvds."""
        from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

        nr, nc = 3, 5
        rng = np.random.default_rng(0)
        mv = np.zeros((nr, nc, 2), np.int32)
        luma = np.zeros((nr, nc, 16, 16), np.int32)
        cbd = np.zeros((nr, nc, 4), np.int32)
        cba = np.zeros((nr, nc, 4, 15), np.int32)
        crd = np.zeros((nr, nc, 4), np.int32)
        cra = np.zeros((nr, nc, 4, 15), np.int32)
        # row 0: pure skip; row 1: motion+levels; row 2: extremes
        mv[1] = rng.integers(-39, 40, (nc, 2))
        luma[1] = rng.integers(-3, 4, (nc, 16, 16))
        cba[1, ::2] = rng.integers(-2, 3, (cba[1, ::2].shape))
        mv[2, 0] = (39, -39)
        luma[2, 0, 0, 0] = 141          # largest in-budget |level|
        luma[2, 0, 0, 5] = -141
        luma[2, 1, 3, :] = rng.integers(-20, 21, 16)
        cbd[2, 2] = (7, -7, 1, 0)
        dense = {"mv": mv, "luma": luma, "cb_dc": cbd, "cb_ac": cba,
                 "cr_dc": crd, "cr_ac": cra}
        want = h264_cabac.encode_p_picture(dense, qp=30, frame_num=2)
        buf = np.asarray(cabac_binarize.binarize_p(
            mv, luma, cbd, cba, crd, cra))
        got = h264_cabac.encode_p_from_binstream(
            buf, nr=nr, nc_mb=nc, qp=30, frame_num=2)
        assert got is not None and got == want

    def test_p_overflow_flag_on_giant_level(self):
        """A |level| beyond the suffix budget must set the overflow
        flag (the caller then re-encodes dense) — never corrupt."""
        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize

        nr, nc = 2, 2
        luma = np.zeros((nr, nc, 16, 16), np.int32)
        luma[0, 0, 0, 0] = 500
        buf = np.asarray(cabac_binarize.binarize_p(
            np.zeros((nr, nc, 2), np.int32), luma,
            np.zeros((nr, nc, 4), np.int32),
            np.zeros((nr, nc, 4, 15), np.int32),
            np.zeros((nr, nc, 4), np.int32),
            np.zeros((nr, nc, 4, 15), np.int32)))
        assert int(buf[1]) == 1
        assert cabac_binarize.split_rows(buf, nr) is None

    def test_intra_byte_identical_incl_i4(self):
        """Intra byte-identity on real device-stage levels (auto mode
        set, so I_NxN MBs are in the mix when content asks for them)."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.bitstream import h264_cabac
        from docker_nvidia_glx_desktop_tpu.ops import (cabac_binarize,
                                                       h264_device)

        w, h = 128, 96
        f0 = _yuv(conftest.make_test_frame(h, w, seed=5), w, h)
        lv = h264_device.encode_intra_frame_yuv(
            *[jnp.asarray(p) for p in f0], 26)
        dense = {k: np.asarray(v) for k, v in lv.items()
                 if not k.startswith("recon")}
        want = h264_cabac.encode_intra_picture(
            dense, qp=26, frame_num=0, idr_pic_id=1, sps=b"S", pps=b"P")
        buf = np.asarray(cabac_binarize.binarize_intra(
            lv["luma_dc"], lv["luma_ac"], lv["cb_dc"], lv["cb_ac"],
            lv["cr_dc"], lv["cr_ac"], lv["pred_mode"], lv["mb_i4"],
            lv["i4_modes"], lv["luma_i4"]))
        got = h264_cabac.encode_intra_from_binstream(
            buf, nr=h // 16, nc_mb=w // 16, qp=26, frame_num=0,
            idr_pic_id=1, sps=b"S", pps=b"P")
        assert got is not None
        assert got == want

    @pytest.mark.parametrize("how", ["constructed", "served"])
    def test_serving_paths_agree(self, how):
        """H264Encoder entropy='cabac' with device binarization (what
        it chooses) must emit the exact bytes the level transport
        does, GOP-deep through the pipelined API: constructed
        directly at a fixed qp, and as ``make_encoder`` serves it under
        the environment of benchmark/configs/desk1080-cabac.json (qp
        traced, the rate controller moving it)."""
        import json
        import pathlib

        from docker_nvidia_glx_desktop_tpu.models import make_encoder
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.utils.config import from_env

        frames = [np.ascontiguousarray(np.roll(
            conftest.make_test_frame(96, 128, seed=9), 2 * i, axis=1))
            for i in range(4)]
        env = json.loads((pathlib.Path(__file__).resolve().parents[1]
                          / "benchmark" / "configs"
                          / "desk1080-cabac.json").read_text())["env"]

        def run(mode):
            if how == "served":
                enc, _ = make_encoder(from_env(dict(
                    env, SIZEW="128", SIZEH="96", PASSWD="pw",
                    ENCODER_GOP="4")), 128, 96)
                assert enc._dyn_qp and enc._rate is not None
            else:
                enc = H264Encoder(128, 96, qp=26,
                                  entropy="cabac", gop=4, deblock=True)
            assert enc.cabac_device_binarize       # chosen from the tune
            enc._cabac_dev_bin = mode == "device"  # the test's hook
            out = []
            pend = []
            i = 0
            while len(out) < len(frames):
                while i < len(frames) and len(pend) < 2:
                    pend.append(enc.encode_submit(frames[i]))
                    i += 1
                out.append(enc.encode_collect(pend.pop(0)).data)
            return out

        dev = run("device")
        host = run("host")
        assert [len(d) for d in dev] == [len(h) for h in host]
        assert dev == host


class TestAlternateLineSad:
    def test_exact_shift_found_by_both(self):
        """A clean even-pel roll must yield the exact dominant MV on
        the alternate-line SAD scale, from both programs the per-frame
        paths call (qp static, qp traced)."""
        import jax.numpy as jnp

        from docker_nvidia_glx_desktop_tpu.ops import h264_inter

        base = conftest.make_test_frame(64, 96, seed=12)
        f0 = _yuv(base, 96, 64)
        f1 = _yuv(np.ascontiguousarray(np.roll(base, 4, axis=1)), 96, 64)
        for program, qp in ((h264_inter.encode_p_frame, 26),
                            (h264_inter.encode_p_frame_dynqp,
                             np.int32(26))):
            out = program(
                *[jnp.asarray(p) for p in f1],
                *[jnp.asarray(p) for p in f0], qp=qp)
            inner = np.asarray(out["mv"])[:, 1:-1]
            dom = np.bincount(
                (inner[..., 1].astype(int) + 39).ravel()).argmax() - 39
            assert dom == -16, (program, dom)


class TestDeblockKernel:
    @pytest.mark.parametrize("traced_qp", [False, True],
                             ids=["static_qp", "traced_qp"])
    @pytest.mark.parametrize("kind", ["intra", "p"])
    @pytest.mark.parametrize(
        "h,w,qp", [(96, 128, 26), (96, 160, 40), (2064, 32, 33)],
        ids=["96x128", "96x160", "2064x32"])
    def test_kernel_scan_and_reference_byte_equal(self, h, w, qp, kind,
                                                  traced_qp, rng,
                                                  monkeypatch):
        """The TPU schedule (one Pallas kernel, here in interpret mode
        and reached through ``deblock_frame``'s own backend test) must be
        byte-identical to the CPU's scan AND to the numpy spec-order
        reference: intra and P bS, a static and a traced qp, an ``nc``
        that is a whole column block (8) and one that is not (10), and
        more MB rows (129) than one block of lanes holds."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import h264_deblock as d
        from docker_nvidia_glx_desktop_tpu.ops.quant import chroma_qp

        nr, nc = h // 16, w // 16
        # smooth ramps under noise, so that the strong filter and both
        # tc branches run, beside pure noise that mostly fails alpha
        y = (np.add.outer(np.arange(h), np.arange(w)) // 3
             + rng.integers(0, 7, (h, w))).astype(np.uint8)
        y[: h // 2] = rng.integers(0, 256, (h // 2, w))
        cb = rng.integers(100, 140, (h // 2, w // 2)).astype(np.uint8)
        cr = rng.integers(0, 256, (h // 2, w // 2)).astype(np.uint8)
        nnz = rng.integers(0, 2, (nr, nc, 4, 4)).astype(bool)
        mv = rng.integers(-20, 21, (nr, nc, 2)).astype(np.int32)
        if kind == "p":
            kw = {"nnz_blk": jnp.asarray(nnz), "mv": jnp.asarray(mv)}
            bs_v, bs_h = d.p_bs(nnz, mv)
        else:
            kw = {}
            bs_v, bs_h = d.intra_bs(nr, nc)
        ref = d.deblock_frame_ref(y, cb, cr, qp, chroma_qp(qp), bs_v, bs_h)
        assert (ref[0] != y).mean() > 0.05      # the filter did work

        scan = (d.deblock_frame_dynqp(y, cb, cr, jnp.int32(qp), **kw)
                if traced_qp else d.deblock_frame(y, cb, cr, qp, **kw))
        # a fresh jit of the same body: the one above is traced already
        body = jax.jit(d.deblock_frame.__wrapped__,
                       static_argnames=() if traced_qp else ("qp",))
        with monkeypatch.context() as mp, pltpu.force_tpu_interpret_mode():
            mp.setattr(jax, "default_backend", lambda: "tpu")
            kernel = body(y, cb, cr, jnp.int32(qp) if traced_qp else qp,
                          **kw)
        for pk, ps, pr in zip(kernel, scan, ref):
            np.testing.assert_array_equal(np.asarray(pk), pr)
            np.testing.assert_array_equal(np.asarray(ps), pr)


class TestMeshSharedDeblock:
    def test_sharded_p_deblock_matches_monolithic(self):
        """h264_p_batch_step(deblock=True): per-shard filtering of a
        contiguous MB-row block must equal whole-frame filtering (the
        idc=2 slice-per-row contract), GOP-deep with live halos."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-virtual-device CPU backend")
        from docker_nvidia_glx_desktop_tpu.parallel import batch

        batch.dryrun_full_geometry(4, h=96, w=64, gop_p=2)
