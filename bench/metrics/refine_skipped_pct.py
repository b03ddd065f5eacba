"""Refinement: the share of refinement repetitions skipped on the device
because an earlier repetition of their half-level applied no move,
100 × Σ ``skipped_reps`` / Σ ``reps`` over the window solves'
``refine_level`` spans (counted on the device, read once per solve).
Nothing when the spans carry no such counts."""
import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    levels = [s for t in spans.named(roots, "refine_level") for s in t]
    if not levels or any("skipped_reps" not in s.attrs for s in levels):
        return None
    reps = sum(s.attrs["reps"] for s in levels)
    return 100.0 * sum(s.attrs["skipped_reps"] for s in levels) / reps
