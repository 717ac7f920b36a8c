"""How old a display frame was when the session loop took it (taken minus due,
``t0 + k/60``), median over the window; ``g2g_p50_ms`` carries it whole.
Since PR 33 a turn with time left ends on the display's next frame, and this
is the step of that wait (0.35-0.5 ms) plus what the turns after an overrun
carry; where every turn is over the refresh (1600p, 4K30) it is the drift of
the turn against the refresh, 10-12 ms.  Until PR 33 it was a SAWTOOTH in
every cell: the loop slept out the refresh on a clock of its own, slid
against the display by 0.1-0.2 ms a turn, and the age swept 0-16.7 ms every
second or two (never "a phase that settles", as PR 26 to 32 wrote)."""
from benchmark import stats


def read(run):
    age = run.get("capture_age_ms")
    return stats.percentile(age, 50) if age else None
