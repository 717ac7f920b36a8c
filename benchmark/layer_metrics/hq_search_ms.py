"""The motion search and compensation whole, under lambda-scaled margins:
device self time a frame under ``dngd.me_int`` + ``dngd.me_subpel`` +
``dngd.mc``."""
from benchmark.layer_metrics import _hq


def read(run):
    return _hq.search_ms(run)
