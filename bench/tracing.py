"""Reduction of a profiler trace to the benchmark's device numbers.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain form: the device operations of each chip (line ``XLA Ops`` of each
``/device:TPU:<i>`` plane; an operation's name is its HLO text, and control
flow such as ``while`` encloses the operations of its body), the compiled
programs they ran in (line ``XLA Modules``) and the host's annotation
spans (the program's ``obs.trace`` spans and the harness's own
``bench_window``), each as ``[name, start_ns, duration_ns]``. Everything
else works on that plain form, so the tests can feed it a small recorded
trace.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re

WINDOW = "bench_window"   # the harness's annotation around the window


def from_xplane(log_dir: str, host_names: set[str]) -> dict:
    """The plain form of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, modules, host = {}, {}, []
    names: dict[str, str] = {}  # one string per distinct name
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device.setdefault(plane.name, [])
            mods = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    (ops if line.name == "XLA Ops" else mods).extend(
                        [names.setdefault(e.name, e.name), e.start_ns,
                         e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in host_names)
    return dict(device=device, modules=modules, host=host)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def window_bounds(trace: dict) -> tuple[float, float]:
    """[start, end) of the harness's window span, in ns on the trace's
    clock."""
    spans = [h for h in trace["host"] if h[0] == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    _, t0, dur = spans[-1]
    return t0, t0 + dur


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def union(events, t0, t1) -> list[tuple[float, float]]:
    """Merged busy intervals of ``events`` inside [t0, t1)."""
    merged: list[list[float]] = []
    for _, a, b in sorted(_clip(events, t0, t1), key=lambda x: x[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran on a chip inside the window,
    averaged over the chips."""
    t0, t1 = window_bounds(trace)
    planes = list(trace["device"].values())
    if not planes:
        return 0.0
    return sum(sum(b - a for a, b in union(ops, t0, t1))
               for ops in planes) / len(planes) / 1e9


def op_time(trace: dict, match) -> tuple[int, float]:
    """(calls, device seconds) of the window's operations whose name
    satisfies ``match``, summed over the chips."""
    t0, t1 = window_bounds(trace)
    n, total = 0, 0.0
    for ops in trace["device"].values():
        for name, a, b in _clip(ops, t0, t1):
            if match(name):
                n += 1
                total += (b - a) / 1e9
    return n, total


def op_label(name: str) -> str:
    """An operation's HLO instruction name and opcode, without operands."""
    instr, _, rest = name.partition(" = ")
    m = re.search(r"(?<![A-Za-z0-9_])([a-z][a-z0-9_\-]*)\(", rest)
    return f"{instr} {m.group(1)}" if m else instr


def _self_times(ops, t0, t1):
    """(name, self seconds) of each operation inside [t0, t1): its time
    less that of the operations nested in it on the same line."""
    evs = sorted(_clip(ops, t0, t1), key=lambda x: (x[1], -x[2]))
    self_ns = [b - a for _, a, b in evs]
    stack: list[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack:  # nested (or, off one line, overlapping): the overlap
            self_ns[stack[-1]] -= min(b, evs[stack[-1]][2]) - a
        stack.append(i)
    return [(evs[i][0], evs[i][1], self_ns[i] / 1e9) for i in range(len(evs))]


def top_ops(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time in the window, by
    self time (time less that of nested operations), summed over calls and
    chips; each named ``<program> <instruction> <opcode>``."""
    t0, t1 = window_bounds(trace)
    acc: dict[str, float] = {}
    for plane, ops in trace["device"].items():
        mods = sorted((m[1], m[1] + m[2], re.sub(r"\(\d+\)$", "", m[0]))
                      for m in trace.get("modules", {}).get(plane, []))
        starts = [m[0] for m in mods]
        for name, a, sec in _self_times(ops, t0, t1):
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            label = f"{mod} {op_label(name)}"
            acc[label] = acc.get(label, 0.0) + sec
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """Device idle time inside the window, by the innermost host span open
    at each gap's midpoint (``idle`` where none is): the ``k`` largest
    totals, in seconds, on the first chip."""
    t0, t1 = window_bounds(trace)
    planes = list(trace["device"].values())
    busy = union(planes[0], t0, t1) if planes else []
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    # sweep in time order with the open spans as a stack (they nest:
    # one thread opens them), so a gap goes to the innermost open span
    marks = []
    for i, (n, s, d) in enumerate(trace["host"]):
        if n != WINDOW:
            marks += [(s, 0, i), (s + d, 1, i)]
    marks += [((a + b) / 2, 2, j) for j, (a, b) in enumerate(gaps)]
    acc: dict[str, float] = {}
    stack: list[int] = []
    for _, kind, i in sorted(marks):
        if kind == 0:
            stack.append(i)
        elif kind == 1:
            stack.remove(i)
        else:
            a, b = gaps[i]
            name = trace["host"][stack[-1]][0] if stack else "idle"
            acc[name] = acc.get(name, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


# the program's Pallas kernels: a kernel's device operation is named after
# the jitted function that calls ``pallas_call`` (``%gains_pallas.3 =
# (...) custom-call(...), custom_call_target="tpu_custom_call"``)
KERNELS = {"gains": "gains_pallas", "pair_scores": "pair_scores_pallas",
           "pins_count": "pins_count_pallas"}


def is_kernel(name: str, kernel: str | None = None) -> bool:
    """Whether a device operation is one of the program's kernels (or the
    one named)."""
    instr = name.partition(" = ")[0]
    keys = [KERNELS[kernel]] if kernel else KERNELS.values()
    return any(re.fullmatch(rf"%{k}(\.\d+)?", instr) for k in keys)
