"""RGB to padded YUV 4:2:0 planes per frame (cv2 on the host, else the device
conversion's dispatch): the program's stage span ``colour``,
``dngd_stage_colour_ms`` (models/h264.py), over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_colour_ms")
