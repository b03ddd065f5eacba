"""Device: seconds in which the first chip ran no operation while the host
sat in one of the program's ``device_sync`` spans, mean per window solve.
The spans are placed on the trace's clock by the ``partition`` event each
solve's tree shares with the trace (``spans.on_trace_clock``); the largest
gap of that placement from the trace's own span events goes to standard
error. Nothing when the program has no such span."""
import sys

import spans
import tracing


def idle(trace: dict) -> list[tuple[float, float]]:
    """The first chip's idle intervals inside the window, in ns."""
    t0, t1 = tracing.window_bounds(trace)
    planes = trace["device"]
    ops = planes.get("/device:TPU:0") or next(iter(planes.values()), [])
    gaps, cur = [], t0
    for a, b in tracing.union(ops, t0, t1):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def overlap_ns(gaps, intervals) -> float:
    """Length of the overlap of two lists of disjoint intervals."""
    total, i = 0.0, 0
    for a, b in sorted(intervals):
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            total += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    return total


def read(run):
    roots = spans.trees(run)
    if roots is None or run.trace is None:
        return None
    placed = spans.on_trace_clock(roots, run.trace)
    if placed is None:
        return None
    syncs = [[(a, b) for sp, a, b in t if sp.name == "device_sync"]
             for t in placed]
    if not any(syncs):
        return None
    res = spans.residual_ns(placed, run.trace)
    print("spans on the trace's clock: largest gap from the trace's span "
          f"events {'not read' if res is None else f'{res / 1e6:.4f} ms'}",
          file=sys.stderr)
    gaps = idle(run.trace)
    return sum(overlap_ns(gaps, s) for s in syncs) / len(syncs) / 1e9
