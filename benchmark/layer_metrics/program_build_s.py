"""Seconds spent building programs the cache did not serve, from process
start to the start of the window: tracing and lowering
(``dngd_jax_trace_lower_seconds_total``) plus the backend's compiles
(``dngd_jax_backend_compile_seconds_total``)."""


def read(run):
    start = run["counters_start"]
    lower = start.get("dngd_jax_trace_lower_seconds_total")
    build = start.get("dngd_jax_backend_compile_seconds_total")
    return None if lower is None or build is None else lower + build
