"""Device self time a frame of the CABAC path outside the search, the
binarize programs and the loop filter: ``mc``, ``tq``, ``recon``, ``ingest``,
the IDR's ``intra``, ``frame_stats``, ``level_pack`` and what lies under no
scope.  With ``cabac_binarize_ms``, ``cabac_search_ms`` and the program
``jit_deblock_frame`` it sums to ``device_ms_per_frame`` less the programs'
time between their operations."""
from benchmark.layer_metrics import _cabac, _stages


def read(run):
    return _stages.scopes_ms(
        run, lambda scope: scope != _cabac.BINARIZE
        and scope not in _cabac.SEARCH)
