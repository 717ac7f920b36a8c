"""Chip 0's device self time a frame under no ``dngd.`` scope in the mesh's
shard programs: the copies and layout changes the compiler adds (one-chip 4K:
2.6 ms in the P program and 2.5 in the binarize program, PERF.md section 5)."""
from benchmark.layer_metrics import _mesh


def read(run):
    return _mesh.unscoped_ms(run)
