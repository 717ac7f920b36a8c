"""KiB of record stream (or of packed levels, under host binarization) pulled
from the chip per frame: ``dngd_encoder_cabac_record_bytes_total`` /
``dngd_encoder_frames_total`` over the window.  Nothing from a program
without the counter."""
from benchmark.layer_metrics import _counters


def read(run):
    pulled = _counters.delta(run, "dngd_encoder_cabac_record_bytes_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return pulled / 1024.0 / frames if pulled is not None and frames else None
