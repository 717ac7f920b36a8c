"""The macroblocks of the window's P frames the search lost: coded I_16x16
because the motion candidate lost to intra by ``SSD + lambda * bits``
(``dngd_encoder_p_intra_mbs_total`` / ``dngd_encoder_p_mbs_total``).  A
witness of the mechanism, not a goal."""
from benchmark.layer_metrics import _hq


def read(run):
    share = _hq.per_p_mb(run, _hq.P_INTRA_MBS)
    return None if share is None else 100.0 * share
