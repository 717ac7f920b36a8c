"""The two collectives' share of their roofline: the least time the chip's
interconnect could take for the bytes one chip receives a frame
(``peaks.json``'s ``ici_bits_per_s`` / 8) over the time chip 0 spends under
``dngd.halo`` + ``dngd.gather``.  Bound by bytes; a few percent is what small,
latency-bound transfers give, and the scopes hold the halo's selects and
concatenation too.  Over 100 the bytes are counted too high."""
from benchmark.layer_metrics import _mesh


def read(run):
    moved = _mesh.collective_bytes_per_frame(run)
    spent = _mesh.scopes_ms(run, {_mesh.HALO, _mesh.GATHER}.__contains__)
    peak = _mesh.ici_bytes_per_s(run) if moved and spent else None
    if not peak:
        return None
    return 100.0 * (moved / peak) / (spent / 1e3)
