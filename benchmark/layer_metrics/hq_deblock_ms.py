"""The loop filter with a threshold word a line of every edge: device time a
frame of the program ``jit_deblock_frame``, whole (its scopes, ``dngd.
deblock_thr`` among them, and what lies under none)."""
from benchmark.layer_metrics import _hq, _stages  # noqa: F401 (_hq: the program's word)


def read(run):
    return _stages.program_ms(run, _stages.DEBLOCK_PROGRAM)
