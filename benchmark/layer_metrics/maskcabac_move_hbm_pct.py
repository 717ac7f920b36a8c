"""The gather's and the scatter's share of their roofline under the CABAC
stream: the least time the chip's memory (``peaks.json``'s
``hbm_bytes_per_s``) could take for the bytes the traced frames' worklists
must move (``_mask.row_move_bytes`` a coded row; the rows by the row
programs' names, ``jit_encode_p_rows_cabac_b<bucket>``) over the time the chip
spent under ``dngd.mask_gather`` + ``dngd.mask_scatter`` in those frames.
Bound by bytes; over 100 the bytes are counted too high.  Nothing where no
frame of the traced span went through a row program."""
from benchmark.layer_metrics import _maskcabac


def read(run):
    share = _maskcabac.move_hbm_share(run)
    return None if share is None else 100.0 * share
