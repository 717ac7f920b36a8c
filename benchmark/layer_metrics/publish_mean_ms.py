"""The session loop's tail for a delivered frame, after the muxer: counters,
the hand-over to the event loop, the journey, the marks, the content record,
the energy gauges (the end of ``StreamSession._run``'s collect branch): the
program's stage span ``publish``, ``dngd_stage_publish_ms``, over the window.
Nothing from a program without the span."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_publish_ms")
