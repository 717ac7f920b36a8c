"""The gather's and the scatter's share of their roofline: the least time the
chip's memory (``peaks.json``'s ``hbm_bytes_per_s``) could take for the bytes
the traced frames' worklists must move (``_mask.row_move_bytes`` a coded row:
the reference bands in, the recon rows out, a byte a sample; the rows by the
row programs' names, ``_mask.traced_rows``) over the time the chip spent
under ``dngd.mask_gather`` + ``dngd.mask_scatter`` in those frames.  Bound by
bytes, and the only new device work of the mask; a few per cent says that
the move costs a whole frame's pad whatever the worklist.  Over 100 the bytes
are counted too high."""
from benchmark.layer_metrics import _mask


def read(run):
    share = _mask.move_hbm_share(run)
    return None if share is None else 100.0 * share
