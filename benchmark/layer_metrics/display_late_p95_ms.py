"""How late the display swapped its frames in (swap minus due), 95th
percentile over the window: a starved load generator must not read as a slow
server."""
from benchmark import stats


def read(run):
    late = run["display_late_ms"]
    return stats.percentile(late, 95) if late else None
