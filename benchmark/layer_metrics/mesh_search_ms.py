"""The motion search of ONE shard (34 x 240 macroblocks at 4K on four chips):
chip 0's device self time a frame under ``dngd.me_int`` + ``dngd.me_subpel`` of
the mesh's P program; beside the one-chip 4K cell's 16.1 ms and the 1080p
cell's 3.3 it says whether a stage grows with a picture's rows or its
columns (PERF.md section 5)."""
from benchmark.layer_metrics import _mesh


def read(run):
    return _mesh.scopes_ms(run, _mesh.SEARCH.__contains__)
