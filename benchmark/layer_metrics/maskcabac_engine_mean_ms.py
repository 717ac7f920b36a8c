"""The host arithmetic engine a frame of the masked CABAC path: the stage span
``engine`` (``dngd_stage_engine_ms``: the native rows of ``native/cabac.cpp``
over the PLANNED rows' record streams on a frame of the row program, over
every row on an IDR or a dense frame; inside ``assemble``), over the window.
To be held beside ``cabac_engine_mean_ms`` of the dense CABAC cells.  Nothing
from a program without the span."""
from benchmark.layer_metrics import _counters, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    return _counters.mean_ms(run, "dngd_stage_engine_ms")
