"""Binarization and ctxIdx derivation on the chip over the row BAND
(``jit_binarize_p`` at the bucket's shape, and the IDR's
``jit_binarize_intra``): device self time a frame under ``dngd.binarize``,
over every program of the traced span.  Whether it shrinks with the rows or
has a floor is the finding.  Nothing where the traced span holds no such
scope."""
from benchmark.layer_metrics import _maskcabac


def read(run):
    return _maskcabac.scopes_ms(run, _maskcabac.BINARIZE.__eq__)
