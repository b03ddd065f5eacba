"""Entry and host: the program's ``setup`` (capacities, the host-side
expansion audit, the upload) and ``audit`` (parts to the host, compaction,
``core/metrics.audit``) spans, mean seconds per window solve."""


def read(run):
    return sum(s["spans"]["setup"] + s["spans"]["audit"]
               for s in run.solves) / len(run.solves)
