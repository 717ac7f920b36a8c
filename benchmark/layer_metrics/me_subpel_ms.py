"""The sub-pel motion search (half-pel planes, half and quarter refinement):
device self time a frame under the scope ``dngd.me_subpel``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "me_subpel")
