"""The motion search of the CABAC path's P program (``jit_encode_p_frame``):
device self time a frame under ``dngd.me_int`` and ``dngd.me_subpel``, the
code it shares with the CAVLC path; to be held beside ``me_int_ms`` +
``me_subpel_ms`` of the control cell."""
from benchmark.layer_metrics import _cabac, _stages


def read(run):
    return _stages.scopes_ms(run, _cabac.SEARCH.__contains__)
