"""Structured telemetry: metrics registry, span tracing, V-cycle stats.

Three jit-safe, host-side layers (none ever touches a traced value, so
telemetry on/off is bit-identical — see docs/observability.md):

* `repro.obs.metrics` — labeled counters/gauges/histograms in a
  thread-safe `Registry` (process-global default ``REGISTRY``), with
  snapshot/reset, JSONL + Prometheus export, and the ``--metrics-json``
  dump format (`dump_json` / `PeriodicDumper`).
* `repro.obs.trace` — ``span(name, **attrs)`` wall-time span tree with
  device-drain discipline (``sp.sync``) and `jax.profiler.TraceAnnotation`
  alignment (a profiler trace holds the spans on the device's clock);
  ``device_sync(value, what)``, the span every blocking host read of the
  partitioner runs in.
* `repro.obs.vcycle` — per-level `LevelStats` (structure, capacity
  occupancy, kernel path, refinement moves proposed / applied and no-op
  repetitions, connectivity/balance/distinct-incidence slack) assembled by
  the partitioner entry points onto `PartitionResult.level_stats`.
"""
from repro.obs import metrics, trace, vcycle  # noqa: F401
from repro.obs.metrics import (  # noqa: F401
    REGISTRY,
    PeriodicDumper,
    Registry,
    counter,
    dump_json,
    gauge,
    observe,
)
from repro.obs.trace import device_sync, span  # noqa: F401
from repro.obs.vcycle import LevelStats  # noqa: F401
