"""From the session loop taking a frame from the display to its fragment's
arrival at the client, median over the window's delivered frames:
``g2g_p50_ms`` without the frame's age at capture
(``capture_age_p50_ms``), which is a phase that differs from run to run.
The steadier of the two, so a loss in the pipeline shows here first."""
from benchmark import stats


def read(run):
    ms = run.get("taken_to_glass_ms")
    return stats.percentile(ms, 50) if ms else None
