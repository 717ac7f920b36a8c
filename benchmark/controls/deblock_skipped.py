"""Control: break the guarantee "in-loop deblocking on".

The encoder keeps signalling ``disable_deblocking_filter_idc = 2`` but no
longer loop-filters its reference pictures: the step a PR in search of device
time would be tempted by (the filter is a program of its own per frame).  The
decoder filters, the encoder does not, and the two drift apart.
"""


def apply(session) -> None:
    session.encoder._deblock = lambda y, cb, cr, qp, **kw: (y, cb, cr)
