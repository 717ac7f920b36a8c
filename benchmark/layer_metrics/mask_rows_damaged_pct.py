"""What the traffic asked for: the macroblock rows in which the damage grid
found a change, of the rows of the window's planned P frames
(``dngd_mask_rows_damaged_total`` / ``dngd_mask_rows_total``; a wholly calm
frame still names one row, a frame past the ladder's top all of them).
Nothing from a program without the counters."""
from benchmark.layer_metrics import _mask


def read(run):
    return _mask.share_pct(run, _mask.ROWS_DAMAGED, _mask.ROWS)
