"""An ``all_gather`` of the per-shard entropy buffers on a spatial mesh: chip
0's device self time a frame under ``dngd.gather``.  The per-frame steps
gather nothing since PR 36 (each shard's buffer is pulled from its own chip;
compiled for a v5e 2x2 the gather was an all-reduce over four 36 MB buffers a
4K frame): there this reads 0, and ``dngd_mesh_gather_bytes_total`` with it."""
from benchmark.layer_metrics import _mesh


def read(run):
    return _mesh.gather_ms(run)
