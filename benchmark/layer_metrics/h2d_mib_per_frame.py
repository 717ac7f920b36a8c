"""MiB of host arrays handed to the device per frame (the picture's planes in
``dispatch``): ``dngd_encoder_h2d_bytes_total`` /
``dngd_encoder_frames_total`` over the window.  Nothing from a program
without the counter."""
from benchmark.layer_metrics import _link


def read(run):
    return _link.mib_per_frame(run, "dngd_encoder_h2d_bytes_total")
