"""Share of the window's frames whose device CAVLC buffer overflowed and were
entropy-coded on the host (``dngd_encoder_entropy_overflow_total``)."""
from benchmark.layer_metrics import _counters


def read(run):
    over = _counters.delta(run, "dngd_encoder_entropy_overflow_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return 100.0 * over / frames if over is not None and frames else None
