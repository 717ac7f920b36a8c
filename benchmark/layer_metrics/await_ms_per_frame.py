"""The session thread's slack: milliseconds a delivered frame that its turn
spent waiting for the display's next frame (``StreamSession._await_frame``
whole, the stage span ``await``: ``dngd_stage_await_ms_sum`` /
``dngd_encoder_frames_total`` over the window).  0.0 where no turn has time
left to wait; nothing from a program without the span."""
from benchmark.layer_metrics import _counters


def read(run):
    waited = _counters.delta(run, "dngd_stage_await_ms_sum")
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return waited / frames if waited is not None and frames else None
