"""Share of the traced span in which the device was idle under ONE label of
``stage_reduce``'s idle gaps (``run["stages"]["idle_gaps"]``: a gap between two
program executions goes whole to the innermost ``dngd.*`` host span that covers
over half of it, else to ``between spans``).

Read only from a program whose turn is spans from end to end (the stage span
``await`` is what closed it, PR 38): against an older program a gap at the end
of a turn reads ``between spans`` because nothing covered it, which is another
quantity, so nothing is given."""

AWAIT = "dngd.await"
BETWEEN_SPANS = "between spans"        # stage_reduce's label, by its text
TURN_IS_SPANS = "dngd_stage_await_ms_count"


def idle_pct(run, label: str):
    """0.0 where no gap has the label."""
    st, tr = run.get("stages"), run.get("trace")
    if not st or not tr or not tr["window_s"]:
        return None
    if TURN_IS_SPANS not in run["counters_end"]:
        return None
    idle_s = sum(s for name, s in st["idle_gaps"] if name == label)
    return 100.0 * idle_s / tr["window_s"]
