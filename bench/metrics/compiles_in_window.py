"""Compiles (a backend compile or a fetch from the persistent cache)
inside the measured window, from ``jax.monitoring``; the target is 0."""


def read(run):
    return run.counter.get("window", "backend_compile_duration_count")
