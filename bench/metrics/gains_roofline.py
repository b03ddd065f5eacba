"""Pallas kernels: the gains kernel's share of its roofline, the least
time its calls in the window could take on the chip's peaks
(``roofline.gains_min_s``, from each level's live incidence slots and the
solve's partition count) over their device time in the trace. Nothing
when the kernel did not run."""
import sys

import roofline
import tracing


def read(run):
    n, dev_s = tracing.op_time(run.trace,
                               lambda name: tracing.is_kernel(name, "gains"))
    if not n or dev_s <= 0:
        return None
    pk = roofline.peaks(run.device_kind)
    calls, least = 0, 0.0
    for s in run.solves:
        for pins, nodes, reps in s["levels"]:
            calls += reps
            least += reps * roofline.gains_min_s(pins, nodes, s["kcap"], pk)
    if calls != n:  # a trace that lost events: no reading
        print(f"gains_roofline: {n} calls in the trace, {calls} in the "
              f"solves' kernel paths; not read", file=sys.stderr)
        return None
    return 100.0 * least / dev_s
