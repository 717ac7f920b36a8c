"""MiB copied from the device per frame (the guessed prefix of the transport
buffer with its slack, a second pull, the content statistics):
``dngd_encoder_d2h_bytes_total`` / ``dngd_encoder_frames_total`` over the
window.  Nothing from a program without the counter."""
from benchmark.layer_metrics import _link


def read(run):
    return _link.mib_per_frame(run, "dngd_encoder_d2h_bytes_total")
