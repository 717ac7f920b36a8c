"""How long a frame the device had FINISHED waited for the session thread to
begin its collect: the program's ``dngd_session_ready_wait_ms``
(web/session.py; one sample a collected frame) over the window.  A floor: the
collect's start less the first LOOK that found the frame finished
(``H264Encoder.token_ready`` at the turn's end, at every look of the
end-of-turn wait, at the next turn's top and at the collect's start), so the
true wait is longer by up to the distance to the look before (between a
turn's top and its collect's start that is the whole submit); 0.0 a frame
where the thread waited for the device instead (``pull_mean_ms`` has that
side).  Nothing from a program without the family."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_session_ready_wait_ms")
