"""Refinement: the share of the moves that refinement proposed which it
applied, 100 × Σ ``moves_applied`` / Σ ``moves_proposed`` over the window
solves' ``refine_level`` spans (counted on the device, read once per
solve). Prints the per-level counts of the last solve to standard error.
Nothing when the spans carry no such counts."""
import sys

import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    levels = [s for t in spans.named(roots, "refine_level") for s in t]
    if not levels or any("moves_proposed" not in s.attrs for s in levels):
        return None
    print("refine_level counts of the last window solve (level: proposed "
          "applied noop_reps/reps): " + ", ".join(
              f"{s.attrs['level']}: {s.attrs['moves_proposed']} "
              f"{s.attrs['moves_applied']} "
              f"{s.attrs['noop_reps']}/{s.attrs['reps']}"
              for s in spans.named(roots[-1:], "refine_level")[0]),
          file=sys.stderr)
    proposed = sum(s.attrs["moves_proposed"] for s in levels)
    applied = sum(s.attrs["moves_applied"] for s in levels)
    return 100.0 * applied / proposed if proposed else None
