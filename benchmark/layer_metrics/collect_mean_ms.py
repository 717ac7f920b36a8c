"""Device wait + bitstream pull + AU assembly per frame: the program's
``dngd_encoder_collect_ms`` (web/session.py) over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_encoder_collect_ms")
