"""Bytes over the host-device link per frame, from one of the program's byte
counters over ``dngd_encoder_frames_total``."""
from benchmark.layer_metrics import _counters


def mib_per_frame(run, family: str):
    """Nothing from a program without the counter, or a window without a
    frame."""
    moved = _counters.delta(run, family)
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return moved / 2.0 ** 20 / frames if moved is not None and frames else None
