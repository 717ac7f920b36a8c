"""Slow tier: the kernel profiler fed by the REAL H.264 encode path on
the CPU backend — the ISSUE 16 acceptance shape: per-stage histograms
present for both the intra and P paths, cold-jit separated from steady
state by actual XLA backend-compile events, and the chunk-amortized
ring stages accounted per frame."""

import numpy as np

import conftest  # noqa: F401  (forces the multi-device CPU backend)
from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
from docker_nvidia_glx_desktop_tpu.obs import profile as obsp

W, H = 64, 48


def _frames(n, seed=3):
    r = np.random.default_rng(seed)
    base = r.integers(0, 256, size=(H, W, 3)).astype(np.uint8)
    return [np.ascontiguousarray(np.roll(base, 2 * i, axis=1))
            for i in range(n)]


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


class TestDeviceProfile:

    def test_h264_intra_and_p_histograms(self):
        """Two GOPs through the pipelined encoder must leave submit and
        collect histograms for BOTH frame kinds, every sample labelled
        with the encoder's codec/geometry, and real backend compiles
        observed (this test may hit a warm jit cache under -p no:
        randomly, so the compile count is >= 0 but the phase labels
        must still be internally consistent)."""
        obsp.PROFILER.clear()
        enc = H264Encoder(W, H, entropy="device",
                          host_color=True, gop=5)
        out = _drive(enc, _frames(11))
        assert len(out) == 11

        summary = obsp.PROFILER.stage_summary()
        for stage in ("intra-submit", "intra-collect",
                      "p-submit", "p-collect"):
            assert stage in summary, f"missing {stage} histogram"
            assert summary[stage]["n"] > 0
            assert summary[stage]["p50"] >= 0.0

        ring = list(obsp.PROFILER._ring)
        assert all(e[4] == enc.codec for e in ring)      # codec label
        assert all(e[5] == f"{W}x{H}" for e in ring)     # geometry
        phases = {e[3] for e in ring}
        assert phases <= {"cold", "steady"}
        # the pipelined steady path must actually reach steady state
        assert "steady" in phases

        snap = obsp.PROFILER.snapshot()
        assert snap["backend"] == "cpu"
        assert set(snap["stage_p50_ms"]) >= {"intra-collect", "p-collect"}

    def test_ring_chunk_collect_amortized(self):
        """With the super-step ring on, the chunk-dispatch collect is
        divided by chunk_len: the biggest recorded ring-collect sample
        must read like ONE frame's collect cost, not like the whole
        chunk's pull.  A ring-off encoder over the same frames provides
        the per-frame yardstick (flushed partial-ring frames keep the
        ``ring`` token kind, so it cannot come from the same encoder)."""
        obsp.PROFILER.clear()
        chunk = 4
        frames = _frames(17)
        kw = dict(entropy="device", host_color=True, gop=9)
        _drive(H264Encoder(W, H, **kw), frames)
        _drive(H264Encoder(W, H, superstep_chunk=chunk, **kw), frames)
        by_stage = {}
        for (_, stage, ms, *_rest) in obsp.PROFILER._ring:
            by_stage.setdefault(stage, []).append(ms)
        ring_ms = sorted(by_stage.get("ring-collect", []))
        perframe = sorted(by_stage.get("p-collect", []))
        assert len(ring_ms) >= chunk
        assert perframe, "ring-off encoder must feed p-collect"
        p50 = perframe[len(perframe) // 2]
        # unamortized, the chunk slot would be ~chunk * p50; amortized it
        # is ~p50 (2x + 5 ms headroom for shared-runner timing noise)
        assert ring_ms[-1] <= p50 * 2.0 + 5.0

    def test_compile_capture_saw_backend_compiles(self):
        """Across the suite's encoder drives at least one real XLA
        backend compile must have been observed by the listener (a
        fresh geometry forces one here if the cache was warm)."""
        before = obsp.PROFILER._compile_seq
        enc = H264Encoder(W + 16, H + 16, entropy="device",
                          host_color=True, gop=3)
        _drive(enc, [np.zeros((H + 16, W + 16, 3), np.uint8),
                     np.full((H + 16, W + 16, 3), 128, np.uint8)])
        assert obsp.PROFILER._compile_seq > before
        cs = obsp.PROFILER.compile_summary()
        assert cs["backend_compiles"] == obsp.PROFILER._compile_seq
        assert cs["total_ms"] > 0.0
