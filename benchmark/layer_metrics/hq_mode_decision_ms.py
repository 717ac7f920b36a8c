"""The Lagrangian decisions of a P frame: device self time a frame under
the scope ``dngd.mode_decision`` (the forced skip and the I_16x16 candidate
of every macroblock, scored by ``SSD + lambda * bits``)."""
from benchmark.layer_metrics import _hq, _stages  # noqa: F401 (_hq: the program's word)


def read(run):
    return _stages.stage_ms(run, "mode_decision")
