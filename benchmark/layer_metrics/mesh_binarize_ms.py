"""The CABAC binarization of ONE shard inside the mesh's programs: chip 0's
device self time a frame under ``dngd.binarize`` (``ops/cabac_binarize`` with
``ops/cabac_pack``'s two kernels, here under ``shard_map``); the one-chip 4K
cell reads 10.8 ms, the 1080p cell 2.0 (PERF.md section 5)."""
from benchmark.layer_metrics import _mesh


def read(run):
    return _mesh.scopes_ms(run, _mesh.BINARIZE.__eq__)
