"""A turn of the session thread that took a frame, from its top to the end of
its own work (capture, submit, the collect of the frame before with its wait
for the device, the muxer, the loop's tail; NOT the wait for the display's next
frame at its end): the program's ``dngd_session_turn_ms`` (web/session.py)
over the window.  Against the refresh interval it says whether the thread can
keep the display's rate.  Nothing from a program without the family."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_session_turn_ms")
