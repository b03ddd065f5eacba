"""Entry and host: the program's blocking host reads, the ``device_sync``
spans of ``repro.obs.trace``, counted per window solve (mean over the
window's solves). Prints the count of each ``what`` to standard error.
Nothing when the program has no such span."""
import collections
import sys

import spans


def read(run):
    roots = spans.trees(run)
    if roots is None:
        return None
    syncs = spans.named(roots, "device_sync")
    if not any(syncs):
        return None
    by_what = collections.Counter(s.attrs.get("what") for t in syncs
                                  for s in t)
    print("host_syncs by what, per window solve: " + ", ".join(
        f"{w} {n / len(syncs):g}" for w, n in by_what.items()),
        file=sys.stderr)
    return sum(len(t) for t in syncs) / len(syncs)
