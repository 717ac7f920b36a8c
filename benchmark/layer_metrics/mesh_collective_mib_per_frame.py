"""MiB one chip receives a frame in the mesh's two collectives (the reference
halo and the gather of the entropy buffers): ``dngd_mesh_halo_bytes_total`` +
``dngd_mesh_gather_bytes_total`` over ``dngd_encoder_frames_total``, counted
by the program from the operands' shapes at dispatch (``_mesh.halo_bytes``,
``_mesh.gather_bytes`` are the arithmetic)."""
from benchmark.layer_metrics import _mesh


def read(run):
    moved = _mesh.collective_bytes_per_frame(run)
    return None if moved is None else moved / 2.0 ** 20
