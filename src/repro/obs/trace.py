"""Host-side span tree: wall-time phase attribution lined up with XLA.

``span("refine_level", level=3)`` is a context manager recording one node of
a per-thread span tree. Spans follow the PR 5/6 timing discipline — a span
that owns device work must drain it before the timer stops, or the time
leaks into the next phase. Either the body already blocks (the partitioner
drivers block at every phase tail) or the caller hands the span its output
value via ``sp.sync(x)`` and the exit path runs ``jax.block_until_ready``
on it before reading the clock.

Each span body is additionally wrapped in ``jax.profiler.TraceAnnotation``,
so a ``jax.profiler`` trace holds every span on the device trace's clock,
beside the device rows. Spans enter no ``jax.named_scope``: the device op
names of a jitted step come from the scopes inside the step alone, not
from whichever span was open when it was first traced.

``device_sync(value, what)`` is the one way the partitioner waits on the
device: it fetches (or blocks on) a device value inside a ``device_sync``
span whose ``what`` attribute names the read, so every blocking host read
of a solve is timed and counted.

Span exit also observes ``span.<name>.s`` into the default metrics registry
(`repro.obs.metrics.REGISTRY`), which is how ``--metrics-json`` dumps carry
per-phase timings; ``aggregate()`` returns per-name count/total/self-time
rollups from the retained trees.

Everything here is host-side Python; spans never touch traced values (a
``span()`` inside a jitted function would record trace time, not run time —
don't do that), so telemetry on/off cannot change any computed result.
"""
from __future__ import annotations

import contextlib
import collections
import dataclasses
import threading
import time

SPAN_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                float("inf"))

# retained completed root spans (newest last); bounded so a long-lived
# service or pytest session cannot grow without bound
MAX_ROOTS = 64

_tls = threading.local()
_lock = threading.Lock()
_roots: collections.deque = collections.deque(maxlen=MAX_ROOTS)


@dataclasses.dataclass
class Span:
    """One completed (or in-flight) region of the span tree."""

    name: str
    attrs: dict
    t0: float
    t1: float | None = None
    children: list = dataclasses.field(default_factory=list)
    _sync: object = None

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else time.perf_counter()) \
            - self.t0

    @property
    def self_time(self) -> float:
        return self.duration - sum(c.duration for c in self.children)

    def sync(self, value):
        """Register device value(s) to ``block_until_ready`` at span exit,
        so their execution time lands in this span. Returns ``value``."""
        self._sync = value
        return value

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def find(self, name: str) -> "Span | None":
        """First descendant (depth-first) named ``name``."""
        for c in self.children:
            if c.name == name:
                return c
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def to_dict(self) -> dict:
        return dict(name=self.name, attrs=dict(self.attrs),
                    start_s=self.t0, duration_s=self.duration,
                    children=[c.to_dict() for c in self.children])


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record one span; nests under the innermost open span of this thread.
    Yields the `Span` — use ``sp.sync(device_value)`` when the body does not
    already drain its device work, and ``sp.annotate(k=v)`` for attributes
    known only mid-body."""
    import jax

    sp = Span(name=name, attrs=attrs, t0=0.0)
    st = _stack()
    st.append(sp)
    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    sp.t0 = time.perf_counter()
    try:
        yield sp
    finally:
        if sp._sync is not None:
            jax.block_until_ready(sp._sync)
            sp._sync = None
        sp.t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        st.pop()
        if st:
            st[-1].children.append(sp)
        else:
            with _lock:
                _roots.append(sp)
        from repro.obs import metrics
        metrics.observe(f"span.{name}.s", sp.duration, buckets=SPAN_BUCKETS)


def device_sync(value, what: str, read=None):
    """Wait for the device inside a ``device_sync`` span (attribute
    ``what``) and return what the host got: ``jax.device_get(value)`` by
    default, else ``read(value)`` (``jax.block_until_ready`` to block
    without a copy, or a function that reads device arrays itself)."""
    import jax

    with span("device_sync", what=what):
        return (read or jax.device_get)(value)


def roots() -> list:
    """Completed root spans, oldest first (bounded at MAX_ROOTS)."""
    with _lock:
        return list(_roots)


def last_root(name: str | None = None) -> Span | None:
    """Most recent completed root span (optionally of a given name)."""
    with _lock:
        for sp in reversed(_roots):
            if name is None or sp.name == name:
                return sp
    return None


def reset() -> None:
    with _lock:
        _roots.clear()


def aggregate() -> list:
    """Per-name rollup over every retained tree: count, total and self
    seconds — the "spans" section of the metrics dump."""
    acc: dict[str, list] = {}

    def walk(sp: Span) -> None:
        a = acc.setdefault(sp.name, [0, 0.0, 0.0])
        a[0] += 1
        a[1] += sp.duration
        a[2] += max(sp.self_time, 0.0)
        for c in sp.children:
            walk(c)

    for root in roots():
        walk(root)
    return [dict(name=n, count=c, total_s=t, self_s=s)
            for n, (c, t, s) in sorted(acc.items())]
