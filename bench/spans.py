"""The program's span trees of the window's solves (``repro.obs.trace``),
and their place on the profiler trace's clock.

``trees`` returns the ``partition`` roots of the window's solves: the last
``len(run.solves)`` that the program retains, each checked against the
solve's record (``run.record``) by exact equality of its child spans'
durations. A tree that is not the record's (a solve missing, another
``partition`` root in between) gives nothing rather than the wrong tree.

``on_trace_clock`` places a tree's spans on the trace's clock. The program
times spans with ``time.perf_counter``; the trace holds the same
``partition`` span, recorded through ``jax.profiler.TraceAnnotation`` on
the trace's clock. The offset of each solve is the trace event's start
less the tree's ``t0``, so spans that the plain trace leaves out (such as
``device_sync``) can be set beside the device's operations.
"""
from __future__ import annotations

ROOT = "partition"


def trees(run) -> list | None:
    """The window's ``partition`` span trees, oldest first, or nothing
    when they are not the window's solves."""
    from repro.obs import trace as otrace

    n = len(run.solves)
    roots = [r for r in otrace.roots() if r.name == ROOT][-n:] if n else []
    if len(roots) != n or not n:
        return None
    for root, rec in zip(roots, run.solves):
        if {c.name: c.duration for c in root.children} != rec["spans"]:
            return None
    return roots


def walk(sp):
    """A span and its descendants, depth first."""
    yield sp
    for c in sp.children:
        yield from walk(c)


def named(roots, name: str) -> list[list]:
    """Per tree, its spans called ``name``."""
    return [[s for s in walk(r) if s.name == name] for r in roots]


def trace_roots(trace: dict) -> list:
    """The ``partition`` events inside the trace's window, oldest first."""
    import tracing

    t0, t1 = tracing.window_bounds(trace)
    return sorted((h for h in trace["host"]
                   if h[0] == ROOT and t0 <= h[1] < t1), key=lambda h: h[1])


def on_trace_clock(roots, trace: dict) -> list[list] | None:
    """Per tree, ``[span, start_ns, end_ns]`` of each of its spans on the
    trace's clock, or nothing when the trace's window does not hold one
    ``partition`` event per tree."""
    events = trace_roots(trace)
    if len(events) != len(roots):
        return None
    out = []
    for root, (_, start, _) in zip(roots, events):
        off = start - root.t0 * 1e9
        out.append([[s, s.t0 * 1e9 + off, s.t1 * 1e9 + off]
                    for s in walk(root)])
    return out


def residual_ns(placed, trace: dict) -> float | None:
    """The largest gap, in ns, between a placed span's start or end and
    the trace's event for it, over the spans the plain trace also holds
    (matched in order of start, the outer of two that start together
    first, within each solve); nothing when the two
    do not list the same spans."""
    events = trace_roots(trace)
    host = sorted(trace["host"], key=lambda h: (h[1], -h[2]))
    kept = {h[0] for h in host}
    worst = 0.0
    for spans, (_, r0, rdur) in zip(placed, events):
        mine = sorted((p for p in spans if p[0].name in kept),
                      key=lambda p: (p[1], p[1] - p[2]))
        names = {p[0].name for p in mine}
        theirs = [h for h in host if h[0] in names
                  and r0 <= h[1] < r0 + rdur]
        if [p[0].name for p in mine] != [h[0] for h in theirs]:
            return None
        for (_, a, b), (_, s, d) in zip(mine, theirs):
            worst = max(worst, abs(a - s), abs(b - (s + d)))
    return worst
