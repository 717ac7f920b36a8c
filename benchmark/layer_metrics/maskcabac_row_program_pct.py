"""The mask is not dropped: share of the window's planned P frames that a ROW
program coded (``_mask.row_program_share``: the others reached the ladder's
top and went through the dense CABAC programs; IDRs are planned by nothing).
100 on the cell's traffic, whose widest frame is a scroll of 51 of 100 rows.
Nothing from a program without the counters."""
from benchmark.layer_metrics import _mask, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    share = _mask.row_program_share(run)
    return None if share is None else 100.0 * share
