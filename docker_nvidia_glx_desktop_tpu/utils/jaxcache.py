"""Persistent XLA compilation cache placement (shared by the server
entry point, ``chip_smoke.py``, the bench driver and the tests).

Compiles dominate a cold start: one static-qp 1080p program takes about
a minute of host time for the TPU and the served rate ladder has 15 qps
(README "Development").  The cache lives where the operator says —
``JAX_COMPILATION_CACHE_DIR``, JAX's own variable, used exactly as given
— and otherwise at ONE fixed directory inside the checkout.  The path is
part of the cache key story (a directory that moves never hits), so it
is never derived from ``/tmp``, a pid, a time or the backend's name.
"""

from __future__ import annotations

import logging
import os
import pathlib

log = logging.getLogger(__name__)

#: the fixed in-checkout default (listed in .gitignore / .chiprunignore)
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def cache_dir() -> str:
    """Where the persistent cache lives for this process: the
    operator's ``JAX_COMPILATION_CACHE_DIR`` verbatim, else the fixed
    in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def setup_compile_cache() -> str:
    """Enable the persistent compile cache (idempotent; call before the
    first jit compilation — config changes don't invalidate live
    executables) and hook its hit/miss events into the obs registry
    (obs/procstats) so a cold boot is a scrapeable number.  Returns the
    directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already placed the
    cache itself; this helper sets no directory in code then.  One
    WARM/COLD log line states what this boot starts from — pair it with
    procstats.log_startup's hit/miss counts to verify a mounted volume
    works."""
    import jax

    from ..obs.procstats import register_jax_cache_listener
    register_jax_cache_listener()

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # the served programs all compile for far longer than this; the
    # threshold only keeps sub-second trivia (slices, converts) out
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        entries = len(os.listdir(path))
    except OSError:
        entries = 0
    log.info("persistent compile cache at %s: %s (%d entries on disk)",
             path,
             "WARM start" if entries else
             "COLD start — expect minutes of XLA compiles (about a "
             "minute per static-qp 1080p program) and elevated peak RSS",
             entries)
    return path
