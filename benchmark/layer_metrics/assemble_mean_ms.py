"""Host bitstream work after the pull, one sample a frame: Annex-B assembly
(or the overflow fallback's host entropy coding) in the encoder plus the
session's fMP4 muxer: the program's stage span ``assemble``,
``dngd_stage_assemble_ms``, over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_assemble_ms")
