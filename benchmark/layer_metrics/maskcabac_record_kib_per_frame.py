"""KiB of record stream pulled from the chip a frame of the masked CABAC
path: ``dngd_encoder_cabac_record_bytes_total`` /
``dngd_encoder_frames_total`` over the window (a frame of the row program
pulls its band's records, header included, and no more).  To be held beside
``cabac_record_kib_per_frame`` of the dense cells.  Nothing from a program
without the counter."""
from benchmark.layer_metrics import _maskcabac


def read(run):
    pulled = _maskcabac.per_frame(
        run, "dngd_encoder_cabac_record_bytes_total")
    return None if pulled is None else pulled / 1024.0
