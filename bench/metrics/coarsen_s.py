"""Coarsening (``core/coarsen.py``, ``core/contract.py``,
``run_coarsen_loop``): the program's ``coarsen`` span, mean seconds per
window solve. The span blocks on its device work before it closes."""


def read(run):
    return sum(s["spans"]["coarsen"] for s in run.solves) / len(run.solves)
