"""The reference halo of a P frame on a spatial mesh: chip 0's device self
time a frame under ``dngd.halo`` (``parallel/batch._spatial_halo_pad``: the
three planes' ``ppermute``s up and down the mesh, the edge rows' repeats and
the concatenation into the padded reference)."""
from benchmark.layer_metrics import _mesh


def read(run):
    return _mesh.scopes_ms(run, _mesh.HALO.__eq__)
