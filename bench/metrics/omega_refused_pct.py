"""Coarsening: the share of live neighbour slots (merge candidates) whose
merged size exceeds omega, 100 × Σ ``refused_omega`` / Σ ``slots_live``
over the window solves' ``coarsen_level`` spans (counted on the device,
read in each level's one ``level_scalars`` sync). Nothing when the spans
carry no such counts."""
import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    levels = [s for t in spans.named(roots, "coarsen_level") for s in t]
    if not levels or any("refused_omega" not in s.attrs for s in levels):
        return None
    live = sum(s.attrs["slots_live"] for s in levels)
    if not live:
        return None
    return 100.0 * sum(s.attrs["refused_omega"] for s in levels) / live
