"""The content plane's statistics (``jit_frame_stats``): device self time a
frame under the scope ``dngd.frame_stats``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "frame_stats")
