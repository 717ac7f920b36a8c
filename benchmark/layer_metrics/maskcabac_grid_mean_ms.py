"""Host time a planned P frame of the masked CABAC session spends on its
damage grid and row plan, on the session thread in front of ``dispatch``: the
program's stage span ``damage_grid`` (``dngd_stage_damage_grid_ms``,
models/h264.py ``_damage_plan``), over the window.  Nothing from a program
without the span."""
from benchmark.layer_metrics import _counters, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    return _counters.mean_ms(run, "dngd_stage_damage_grid_ms")
