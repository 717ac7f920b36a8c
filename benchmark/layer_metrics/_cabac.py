"""The scopes of the CABAC path's programs that have a reader of their own;
what is left is ``cabac_other_device_ms``."""
from benchmark.stage_reduce import SCOPE_PREFIX

SEARCH = {SCOPE_PREFIX + "me_int", SCOPE_PREFIX + "me_subpel"}
BINARIZE = SCOPE_PREFIX + "binarize"
