"""What the traffic asked for, under the CABAC stream: the macroblock rows in
which the damage grid found a change, of the rows of the window's planned P
frames (``dngd_mask_rows_damaged_total`` / ``dngd_mask_rows_total``, counted
on this path as on the CAVLC mask's).  Nothing from a program without the
counters."""
from benchmark.layer_metrics import _mask, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    return _mask.share_pct(run, _mask.ROWS_DAMAGED, _mask.ROWS)
