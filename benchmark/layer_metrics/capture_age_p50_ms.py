"""How old a display frame was when the session loop took it (taken minus due,
``t0 + k/60``), median over the window.  The loop sleeps out the refresh on a
clock of its own, so this is a phase between two 60 Hz clocks that differs
from run to run, and ``g2g_p50_ms`` carries it whole."""
from benchmark import stats


def read(run):
    age = run.get("capture_age_ms")
    return stats.percentile(age, 50) if age else None
