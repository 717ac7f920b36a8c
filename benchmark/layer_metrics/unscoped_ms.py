"""Device self time a frame under no ``dngd.`` scope, outside the loop filter:
the copies the compiler adds, and the whole of a program that a stale compile
cache served without its scopes."""
from benchmark.layer_metrics import _stages
from benchmark.stage_reduce import NO_SCOPE


def read(run):
    return _stages.scopes_ms(run, NO_SCOPE.__eq__)
