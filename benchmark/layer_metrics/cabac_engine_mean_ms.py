"""The host arithmetic engine of the CABAC path per frame: the program's stage
span ``engine``, ``dngd_stage_engine_ms`` (bitstream/h264_cabac.py: the native
rows of ``native/cabac.cpp`` over the pulled record stream, inside
``assemble``), over the window.  Nothing from a program without the span."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_engine_ms")
