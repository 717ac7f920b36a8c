"""The link probe of ops/devloop (the chunk step's tests are
tests/test_superstep.py's)."""

from docker_nvidia_glx_desktop_tpu.ops import devloop


def test_measure_link_rtt_shape():
    """The serving-budget link probe (obs/budget link separation): a
    dict with a non-negative rtt estimate and its raw samples."""
    out = devloop.measure_link_rtt(reps=3, k_hi=33)
    assert {"rtt_ms", "step_us", "samples"} <= set(out)
    assert out["rtt_ms"] >= 0.0
    assert len(out["samples"]) == 3
    # samples are per-call wall-clocks; the rtt estimate cannot exceed
    # the median sample it was derived from
    assert out["rtt_ms"] <= sorted(out["samples"])[1] + 1e-9
