"""The loop filter: device time a frame of the program ``jit_deblock_frame``,
whole (its scopes and what lies under none)."""
from benchmark.layer_metrics import _stages


def read(run):
    return _stages.program_ms(run, _stages.DEBLOCK_PROGRAM)
