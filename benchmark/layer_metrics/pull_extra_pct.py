"""Share of the window's frames whose bitstream outgrew the guessed prefix
and paid a second device-to-host round trip
(``dngd_encoder_pull_extra_total`` / ``dngd_encoder_frames_total``)."""
from benchmark.layer_metrics import _counters


def read(run):
    extra = _counters.delta(run, "dngd_encoder_pull_extra_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return 100.0 * extra / frames if extra is not None and frames else None
