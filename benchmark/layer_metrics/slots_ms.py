"""CAVLC slot building, in the P and the intra program: device self time a
frame under the scope ``dngd.slots``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "slots")
