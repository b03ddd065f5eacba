"""Seconds of backend compilation (or of fetching from the persistent
cache) during set-up, from ``jax.monitoring``. Tracing and lowering, which
a cache hit does not save, are printed on the set-up line apart."""


def read(run):
    return run.counter.get("setup", "backend_compile_duration")
