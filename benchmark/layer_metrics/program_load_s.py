"""Seconds spent fetching and deserialising executables from the persistent
compile cache, from process start to the start of the window
(``dngd_jax_cache_load_seconds_total``)."""


def read(run):
    return run["counters_start"].get("dngd_jax_cache_load_seconds_total")
