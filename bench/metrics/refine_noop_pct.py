"""Refinement: the share of refinement repetitions that applied no move,
100 × Σ ``noop_reps`` / Σ ``reps`` over the window solves'
``refine_level`` spans (counted on the device, read once per solve).
Nothing when the spans carry no such counts."""
import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    levels = [s for t in spans.named(roots, "refine_level") for s in t]
    if not levels or any("noop_reps" not in s.attrs for s in levels):
        return None
    reps = sum(s.attrs["reps"] for s in levels)
    return 100.0 * sum(s.attrs["noop_reps"] for s in levels) / reps
