"""The longest interval in the window between two frames the session loop
took from the display: one stall of the one host thread, which a rate or a
percentile hides (a 1.4 s hole cost one run 4 frames/s and moved no tail)."""


def read(run):
    return max(run["take_gaps_ms"], default=None)
