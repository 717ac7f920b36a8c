"""The host's row-wise stitch of the shards' record streams into one transport
buffer, per frame: the program's stage span ``stitch``,
``dngd_stage_stitch_ms`` (models/h264.py ``_sp_collect_bin``, inside
``assemble``, in front of the engine), over the window.  Nothing from a
program without the span."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_stitch_ms")
