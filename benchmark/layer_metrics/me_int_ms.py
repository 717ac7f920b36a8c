"""The integer motion search (the candidate grid and its refinement): device
self time a frame under the scope ``dngd.me_int``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "me_int")
