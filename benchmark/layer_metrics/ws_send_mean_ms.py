"""Encode thread's publish to the return of the websocket write, per fragment:
thread hand-off + event-loop queue + socket write
(``dngd_ws_publish_to_send_ms``, stamped in ``StreamSession._post``, closed in
``web/server.py``'s media pump), over the window."""
from benchmark.layer_metrics import _counters


def read(run):
    return _counters.mean_ms(run, "dngd_ws_publish_to_send_ms")
