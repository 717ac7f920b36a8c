"""Share of the window's planned P frames that the ROW program coded (the
others reached the ladder's top and went through the full-frame program;
IDRs are planned by nothing): ``dngd_mask_frames_total{program="rows"}``
over both programs', read through the row counters because ``run.py`` adds a
family's series up (``_mask.row_program_share``).  Nothing from a program
without the counters."""
from benchmark.layer_metrics import _mask


def read(run):
    share = _mask.row_program_share(run)
    return None if share is None else 100.0 * share
