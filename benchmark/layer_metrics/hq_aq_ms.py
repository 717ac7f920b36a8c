"""The adaptive quantization: device self time a frame under the scope
``dngd.aq`` (the macroblocks' activity, the qp plane, the ``mb_qp_delta``
chain and its effective qps), in both programs of a frame."""
from benchmark.layer_metrics import _hq, _stages  # noqa: F401 (_hq: the program's word)


def read(run):
    return _stages.stage_ms(run, "aq")
