"""Share of the traced span in which the device was idle while the session
thread waited for the display's next frame (the idle gaps under the host span
``dngd.await``): idle because the display paces the cell, not because the host
is slow.  0.0 where no turn waits."""
from benchmark.layer_metrics import _idle


def read(run):
    return _idle.idle_pct(run, _idle.AWAIT)
