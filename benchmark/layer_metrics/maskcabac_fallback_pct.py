"""Share of the window's frames that the native engine did not code from the
record stream: both kinds of ``dngd_encoder_cabac_fallback_total`` (``dense``:
the band's record stream overflowed or the engine's cap gave way, the
worklist's levels were scattered to the full frame and the host coder coded
it whole; ``python``: no native engine) / ``dngd_encoder_frames_total``.  0 in
a sound run.  Nothing from a program without the counter."""
from benchmark.layer_metrics import _maskcabac


def read(run):
    fell = _maskcabac.per_frame(run, "dngd_encoder_cabac_fallback_total")
    return None if fell is None else 100.0 * fell
