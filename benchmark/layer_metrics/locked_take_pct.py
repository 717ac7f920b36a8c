"""Share of the window's frames that the session loop took within a look's
step of the display swapping them in: turns whose next frame was found BY the
end-of-turn wait (``dngd_session_locked_takes_total`` /
``dngd_encoder_frames_total``).  0.0 where no turn has time left to wait (the
traffic bypasses the mechanism); nothing from a program without the counter."""
from benchmark.layer_metrics import _counters


def read(run):
    locked = _counters.delta(run, "dngd_session_locked_takes_total")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return 100.0 * locked / frames if locked is not None and frames else None
