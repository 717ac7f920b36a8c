"""The content statistics' pull at the end of ``H264Encoder.encode_collect``
(``_content_finish``: the stats program's vector and grid brought to the host
and decoded), one sample a collected frame: the program's stage span
``stats``, ``dngd_stage_stats_ms``, over the window.  Inside
``collect_mean_ms`` and outside every other stage; nothing from a program
without the span."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_stage_stats_ms")
