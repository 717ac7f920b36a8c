"""The row program's way IN: the chip's device self time a frame under
``dngd.mask_gather`` (the three reference planes padded as int32, WHOLE, and
the worklist's bands cut out of them and out of the frame; the part of a
masked frame that does not shrink with the worklist).  Nothing where no
frame of the traced span went through the row program."""
from benchmark.layer_metrics import _mask


def read(run):
    return _mask.scope_ms(run, _mask.GATHER)
