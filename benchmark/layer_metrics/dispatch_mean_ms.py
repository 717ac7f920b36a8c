"""Host time to hand a frame to the device: H2D of the planes, the P (or
intra) program, the stats program, the loop filter, the prefix slice and its
prefetch: the program's stage span ``dispatch``, ``dngd_stage_dispatch_ms``
(models/h264.py), over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_dispatch_ms")
