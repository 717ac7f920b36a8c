"""The row program's way OUT: the chip's device self time a frame under
``dngd.mask_scatter`` (the worklist's recon rows written back into the three
reference planes).  Nothing where no frame of the traced span went through
the row program."""
from benchmark.layer_metrics import _mask


def read(run):
    return _mask.scope_ms(run, _mask.SCATTER)
