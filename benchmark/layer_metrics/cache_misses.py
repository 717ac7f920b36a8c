"""Compile requests the persistent cache did not serve, from process start to
the start of the window (``jax_compile_cache_misses_total``)."""


def read(run):
    return run["counters_start"].get("jax_compile_cache_misses_total")
