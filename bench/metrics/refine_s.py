"""Refinement (``core/refine.py``, ``run_refine_loop``): the program's
``refine`` span, mean seconds per window solve. The span blocks on its
device work before it closes."""


def read(run):
    return sum(s["spans"]["refine"] for s in run.solves) / len(run.solves)
