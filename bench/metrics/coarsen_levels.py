"""Coarsening: how deep the V-cycle goes, the mean number of
``coarsen_level`` spans per window solve (one per level entered, the last,
which finds no pair to merge, included). Nothing when the window's span
trees are not at hand."""
import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    return sum(map(len, spans.named(roots, "coarsen_level"))) / len(roots)
