"""Every ``dngd.`` scope that has no reader of its own (``mc``, ``tq``,
``recon``, ``ingest``, ``mode_decision``, the P program's ``deblock_bs``, the
IDR's ``intra`` and ``colour``): device self time a frame."""
from benchmark.layer_metrics import _stages
from benchmark.stage_reduce import NO_SCOPE


def read(run):
    return _stages.scopes_ms(
        run, lambda scope: scope != NO_SCOPE and scope not in _stages.NAMED)
