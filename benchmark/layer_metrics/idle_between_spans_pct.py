"""Share of the traced span in which the device was idle and NO host span of
the program covered over half of the gap: host work that nothing names yet, or
a gap that straddles two spans (``stage_reduce`` gives a gap to one span
whole).  0.0 without such a gap."""
from benchmark.layer_metrics import _idle


def read(run):
    return _idle.idle_pct(run, _idle.BETWEEN_SPANS)
