"""The bit pack of the frame, in the P and the intra program: device self time
a frame under the scope ``dngd.pack``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "pack")
