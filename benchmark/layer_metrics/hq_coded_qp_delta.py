"""What the adaptive quantization moved the mean macroblock by: the mean
EFFECTIVE qp of the window's P macroblocks less the mean of their slices' qp
((``dngd_encoder_coded_qp_sum_total`` - ``dngd_encoder_slice_qp_sum_total``)
/ ``dngd_encoder_p_mbs_total``).  Negative: the picture is coded finer than
the rate ladder's rung says.  A witness, not a goal."""
from benchmark.layer_metrics import _hq


def read(run):
    coded, base = (_hq.per_p_mb(run, _hq.CODED_QP_SUM),
                   _hq.per_p_mb(run, _hq.SLICE_QP_SUM))
    return None if coded is None or base is None else coded - base
