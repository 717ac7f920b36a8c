"""Session-continuity tests (ISSUE 4): encoder-state checkpoint/restore
round-trips per codec family, device-preempt recovery on the live
session (same muxer/init-segment lineage, recovery IDR), and elastic
mesh re-bucketing after chip loss.

Encode-bearing (jit compiles), so the module rides the slow tier; the
pure-arithmetic pieces (CheckpointKeeper, replan_mesh, breaker trip)
live in tests/test_resilience.py's fast tier.
"""

import time

import numpy as np
import pytest

from conftest import make_test_frame
from docker_nvidia_glx_desktop_tpu.models import make_encoder
from docker_nvidia_glx_desktop_tpu.resilience import faults
from docker_nvidia_glx_desktop_tpu.utils.config import from_env


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


def _h264_cfg(**extra):
    env = {"SIZEW": "128", "SIZEH": "96", "REFRESH": "30",
           "ENCODER_GOP": "10", "ENCODER_BITRATE_KBPS": "0",
           "ENCODER_PREWARM": "false"}
    env.update(extra)
    return from_env(env)


class TestH264Checkpoint:
    def test_roundtrip_continues_lineage(self, warm_session_codec):
        cfg = _h264_cfg()
        enc, name = make_encoder(cfg, 128, 96)
        frames = [make_test_frame(96, 128, s) for s in range(3)]
        efs = [enc.encode(f) for f in frames]        # IDR + 2 P
        assert [e.keyframe for e in efs] == [True, False, False]

        st = enc.export_state()
        assert st["codec"] == "h264" and st["frame_index"] == 3
        assert st["gop_pos"] == 3 and st["ref"] is not None
        # the checkpoint is host-only: numpy planes, plain ints
        assert all(isinstance(p, np.ndarray) for p in st["ref"])

        enc2, name2 = make_encoder(cfg, 128, 96)
        assert name2 == name
        enc2.import_state(st)
        assert enc2._idr_count == enc._idr_count     # idr_pic_id parity
        ef = enc2.encode(frames[0])
        assert ef.keyframe, "restore must emit a recovery IDR"
        assert ef.frame_index == 3, "frame lineage must continue"
        ef2 = enc2.encode(frames[1])
        assert not ef2.keyframe, "GOP resumes normally after the IDR"

    def test_rate_controller_state_survives(self):
        # no encode needed: the controller state is plain host floats
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        enc = H264Encoder(128, 96, gop=10,
                          bitrate_kbps=4000, fps=30)
        enc._rate.level = 12345.0
        enc._rate._ema[True] = 5000.0
        enc._rate._ema[False] = 900.0
        enc._rate._step_idx = 4
        enc._rate._avg = 1100.0
        enc._rate._pending.append((True, 4))         # in-flight: dropped
        st = enc.export_state()

        enc2 = H264Encoder(128, 96, gop=10,
                           bitrate_kbps=4000, fps=30)
        enc2.import_state(st)
        assert enc2._rate.level == 12345.0
        assert enc2._rate._ema[True] == 5000.0
        assert enc2._rate._step_idx == 4
        assert len(enc2._rate._pending) == 0, \
            "in-flight reservations must not survive the device"

    def test_degrade_bias_survives(self):
        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder

        enc = H264Encoder(128, 96)
        enc.degrade_qp_offset = 4
        enc2 = H264Encoder(128, 96)
        enc2.import_state(enc.export_state())
        assert enc2.degrade_qp_offset == 4


class TestVp8Checkpoint:
    def test_roundtrip_restores_reference(self):
        from docker_nvidia_glx_desktop_tpu.models.vp8 import Vp8Encoder

        f = make_test_frame(48, 64)
        enc = Vp8Encoder(64, 48, q_index=40, gop=3)
        enc.encode(f)
        enc.encode(f)                                # keyframe + inter
        st = enc.export_state()
        assert st["codec"] == "vp8" and st["ref"] is not None

        # rebuilt with a DIFFERENT quality: the checkpointed q_index
        # (and the derived quant factors) must win
        enc2 = Vp8Encoder(64, 48, q_index=50, gop=3)
        enc2.import_state(st)
        assert enc2.core.q_index == 40
        assert np.array_equal(enc2._ref[0], enc._ref[0])
        ef = enc2.encode(f)
        assert ef.keyframe and ef.frame_index == 2


class TestMjpegCheckpoint:
    def test_sticky_tables_survive(self):
        from docker_nvidia_glx_desktop_tpu.models.mjpeg import JpegEncoder

        f = make_test_frame(48, 64)
        enc = JpegEncoder(64, 48, entropy="device", table_mode="sticky")
        data = enc.encode(f).data
        assert data[:2] == b"\xff\xd8"
        st = enc.export_state()
        assert st["tables"] is not None

        enc2 = JpegEncoder(64, 48, entropy="device", table_mode="sticky")
        enc2.import_state(st)
        n0 = enc2._frames_since_tables
        data2 = enc2.encode(f).data
        assert data2[:2] == b"\xff\xd8" and data2[-2:] == b"\xff\xd9"
        assert enc2._frames_since_tables == n0 + 1, \
            "restored sticky tables were rebuilt instead of reused"


class TestDevicePreemptRecovery:
    """Tentpole leg 1 end-to-end: the device-submit breaker trips on a
    preemption, the session re-acquires a device, restores the
    checkpoint, and resumes THE SAME muxer/init-segment lineage with a
    recovery IDR — a glitch, not a teardown."""

    def test_preempt_recovers_same_lineage(self, warm_session_codec):
        from docker_nvidia_glx_desktop_tpu.rfb.source import (
            SyntheticSource)
        from docker_nvidia_glx_desktop_tpu.web.session import StreamSession

        cfg = _h264_cfg(DNGD_CKPT_INTERVAL="0.2")
        sess = StreamSession(cfg, SyntheticSource(128, 96, fps=30))
        posted = []
        sess._post = lambda frag, key, fid=0: posted.append(
            (time.monotonic(), key))
        sess.start()
        try:
            deadline = time.monotonic() + 240
            while not posted and time.monotonic() < deadline:
                time.sleep(0.05)
            assert posted, "no first frame"
            muxer_before = id(sess.muxer)
            init_before = sess.init_segment
            # a checkpoint must exist before the preemption
            deadline = time.monotonic() + 30
            while sess._ckpt.count == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sess._ckpt.count > 0, "no checkpoint taken"

            faults.arm("device_preempt", count=1)
            t0 = time.monotonic()
            deadline = t0 + 60
            while sess._recoveries == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sess._recoveries == 1, "session did not recover"
            # the stream resumes with a keyframe (the recovery IDR)
            deadline = time.monotonic() + 60
            resumed = None
            while resumed is None and time.monotonic() < deadline:
                resumed = next((key for t, key in posted if t > t0), None)
                time.sleep(0.05)
            assert resumed is True, \
                f"first post-recovery frame was not an IDR: {resumed}"
            assert sess._thread.is_alive()
            # lineage: same muxer object, same init segment — the client
            # decodes the recovery IDR against what it already holds
            assert id(sess.muxer) == muxer_before
            assert sess.init_segment == init_before
        finally:
            sess.close()
        assert faults.armed_count("device_preempt") == 0


class TestMeshChipLost:
    """Tentpole leg 2: a chip dropping out of the mesh re-buckets the
    surviving chips and every session keeps delivering."""

    def test_rebucket_and_keep_serving(self):
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("elastic failover test needs >= 4 devices")
        from docker_nvidia_glx_desktop_tpu.rfb.source import (
            SyntheticSource)
        from docker_nvidia_glx_desktop_tpu.web.multisession import (
            BatchStreamManager)

        n = 4
        cfg = from_env({"SIZEW": "128", "SIZEH": "96", "REFRESH": "30",
                        "TPU_SESSIONS": str(n), "TPU_MESH": str(n),
                        "ENCODER_GOP": "1",
                        "ENABLE_BASIC_AUTH": "false"})
        sources = [SyntheticSource(128, 96, fps=30) for _ in range(n)]
        mgr = BatchStreamManager(cfg, sources)
        # pin the elastic pool to the chips actually in the mesh, so the
        # kill hits a member and the re-plan must genuinely shrink
        mgr._all_devices = list(mgr.mesh.devices.reshape(-1))
        posted = {i: [] for i in range(n)}
        idx_of = {id(h): i for i, h in enumerate(mgr.hubs)}

        def rec_post(hub, frag, key, fid=0):
            posted[idx_of[id(hub)]].append((time.monotonic(), key))

        mgr._post = rec_post
        mgr.start()
        try:
            deadline = time.monotonic() + 300
            while (not all(posted.values())
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert all(posted.values()), "not every hub delivered"
            shape_before = tuple(mgr.mesh.devices.shape)

            faults.arm("mesh_chip_lost", count=1)
            deadline = time.monotonic() + 180
            while mgr._rebuilds == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert mgr._rebuilds == 1, "mesh never rebuilt"
            t0 = time.monotonic()
            # every surviving session delivers its recovery keyframe
            # (the rebuilt step recompiles first — allow for that)
            deadline = time.monotonic() + 300
            while (not all(any(t > t0 and key for t, key in v)
                           for v in posted.values())
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert all(any(t > t0 and key for t, key in v)
                       for v in posted.values()), \
                "a session died with the chip"
            stats = mgr.stats_summary()
            assert stats["dead_chips"] == 1
            assert tuple(mgr.mesh.devices.shape) != shape_before, \
                f"mesh did not shrink: {shape_before}"
            assert mgr._thread.is_alive()
        finally:
            mgr.close()
