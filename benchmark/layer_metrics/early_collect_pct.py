"""Share of the window's frames that the session loop collected BETWEEN the
halves of the next frame's submit, behind its colour conversion and in front
of its dispatch, because the device had finished them by then
(``dngd_session_early_collects_total`` / ``dngd_encoder_frames_total``): the
frames whose way to the client lost the next frame's dispatch.  0.0 where the
device is never done by then (the traffic bypasses the mechanism); nothing
from a program without the counter."""
from benchmark.layer_metrics import _counters


def read(run):
    early = _counters.delta(run, "dngd_session_early_collects_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return 100.0 * early / frames if early is not None and frames else None
