"""Making or fetching the slice data a masked CABAC frame's unplanned rows
leave as: the stage span ``skip_slices`` (``dngd_stage_skip_slices_ms``,
bitstream/h264_cabac.py ``encode_p_rows_from_binstream``; inside
``assemble``), a frame of the row program, over the window.  The data depends
on the slice's qp alone, so it is one cache look-up a frame where set-up
warmed the cache (``dngd_encoder_cabac_skip_slices_total{road="cache"}``) and
a run of the Python engine over a row where it did not (``road="coded"``).
Nothing from a program without the span."""
from benchmark.layer_metrics import _counters, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    return _counters.mean_ms(run, "dngd_stage_skip_slices_ms")
