"""The grab of the display's newest frame (``source.frame()`` in
``web/session.py:_run``): the program's stage span ``capture``,
``dngd_stage_capture_ms``, over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_capture_ms")
