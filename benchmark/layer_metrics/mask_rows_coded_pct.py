"""What the chip was given: the rows the device coded, of the rows of the
window's planned P frames, bucket padding and dense frames included
(``dngd_mask_rows_coded_total`` / ``dngd_mask_rows_total``).  Its distance
to ``mask_rows_damaged_pct`` is the power-of-two ladder's price.  Nothing
from a program without the counters."""
from benchmark.layer_metrics import _mask


def read(run):
    return _mask.share_pct(run, _mask.ROWS_CODED, _mask.ROWS)
