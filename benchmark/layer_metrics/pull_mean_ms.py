"""Wait for the device + device-to-host copy of the guessed bitstream prefix:
the program's stage span ``pull``, ``dngd_stage_pull_ms`` (models/h264.py),
over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_pull_ms")
