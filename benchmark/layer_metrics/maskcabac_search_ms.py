"""The motion search of the masked CABAC path (the row program's
``vmap(row)/dngd.me_int`` and ``dngd.me_subpel``, the dense P program's on a
dense frame): device self time a frame, over every program of the traced
span; to be held beside ``cabac_search_ms`` of the dense cells and the
control's ``me_int_ms`` + ``me_subpel_ms``.  Nothing where the traced span
holds no such scope."""
from benchmark.layer_metrics import _maskcabac


def read(run):
    return _maskcabac.scopes_ms(run, _maskcabac.SEARCH.__contains__)
