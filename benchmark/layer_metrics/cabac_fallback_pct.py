"""Share of the window's frames that the native engine did not code: both
kinds of ``dngd_encoder_cabac_fallback_total`` (``dense``: the record stream
overflowed and the levels were pulled whole; ``python``: no native engine) /
``dngd_encoder_frames_total``.  0 in a sound run."""
from benchmark.layer_metrics import _counters


def read(run):
    fell = _counters.delta(run, "dngd_encoder_cabac_fallback_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return 100.0 * fell / frames if fell is not None and frames else None
