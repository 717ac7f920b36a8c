"""Device self time a frame by named stage, from ``run["stages"]``
(``stage_reduce.reduce`` of the traced span): a scope's seconds over the
programs, over the frames.  The loop filter is read as its whole program, so
its scopes and its unscoped part are left out of the others.

No reader gives a number when under nine tenths of the operations' time lies
under a ``dngd.`` scope: a compile cache that a tree without the scopes
filled serves this tree's programs without them (PERF.md section 5), and a
stage would read as the copies the compiler adds."""
from benchmark.stage_reduce import SCOPE_PREFIX

DEBLOCK_PROGRAM = "jit_deblock_frame"
# the scopes with a reader of their own; the rest is other_stages_ms
NAMED = {SCOPE_PREFIX + stage for stage in (
    "me_subpel", "me_int", "slots", "pack", "frame_stats")}
MIN_SCOPED_SHARE = 0.9


def sound(run):
    st = run.get("stages")
    if not st or not st["frames"] or st["scoped_share"] < MIN_SCOPED_SHARE:
        return None
    return st


def scopes_ms(run, pick):
    """ms a frame under the scopes ``pick`` accepts; nothing when none is
    found."""
    st = sound(run)
    if st is None:
        return None
    total = sum(s for name, p in st["programs"].items()
                if name != DEBLOCK_PROGRAM
                for scope, s in p["scopes"].items() if pick(scope))
    return 1e3 * total / st["frames"] if total else None


def stage_ms(run, stage: str):
    return scopes_ms(run, (SCOPE_PREFIX + stage).__eq__)


def program_ms(run, program: str):
    st = sound(run)
    if st is None or program not in st["programs"]:
        return None
    return 1e3 * st["programs"][program]["device_s"] / st["frames"]
