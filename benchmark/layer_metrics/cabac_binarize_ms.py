"""Binarization and ctxIdx derivation on the chip (ops/cabac_binarize, the
programs ``jit_binarize_p`` and ``jit_binarize_intra``): device self time a
frame under the scope ``dngd.binarize``."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.stage_ms(run, "binarize")
