"""What the chip was given, under the CABAC stream: the rows the device coded
AND binarized, of the rows of the window's planned P frames, bucket padding
and dense frames included (``dngd_mask_rows_coded_total`` /
``dngd_mask_rows_total``).  Its distance to ``maskcabac_rows_damaged_pct`` is
the power-of-two ladder's price.  Nothing from a program without the
counters."""
from benchmark.layer_metrics import _mask, _maskcabac  # noqa: F401
# (loading _maskcabac holds the program to the configuration: its docstring)


def read(run):
    return _mask.share_pct(run, _mask.ROWS_CODED, _mask.ROWS)
