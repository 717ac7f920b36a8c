"""The edge kernel's share of its roofline: the least time the chip's memory
(``peaks.json``'s ``hbm_bytes_per_s``) could take for the bytes the loop
filter needs in the traced executions (the planes in and out at a byte a
sample and a macroblock's edge inputs: ``_hq.filter_bytes``, 6.5 MB a 1080p
picture), over the time the chip spent under ``dngd.deblock_edges`` in them.
The kernel works on 32-bit words, lane-sparse at 1080p (68 macroblock rows on
128 lanes), and is bound by its chain of macroblock columns, so about a
tenth; over 100 the bytes are counted too high."""
from benchmark.layer_metrics import _hq


def read(run):
    share = _hq.edges_hbm_share(run)
    return None if share is None else 100.0 * share
