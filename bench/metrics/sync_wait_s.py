"""Entry and host: seconds the host waited inside the program's
``device_sync`` spans (its blocking reads), mean per window solve. Prints
the seconds of each ``what`` to standard error. Nothing when the program
has no such span."""
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
    by_what = collections.Counter()
    for t in syncs:
        for s in t:
            by_what[s.attrs.get("what")] += s.duration / len(syncs)
    print("sync_wait_s by what, per window solve: " + ", ".join(
        f"{w} {v:.6f}" for w, v in by_what.most_common()), file=sys.stderr)
    return sum(s.duration for t in syncs for s in t) / len(syncs)
