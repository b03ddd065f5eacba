#!/usr/bin/env python3
"""The controls and planted faults of the checks, at a cell's own size.

  python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed each graph of the cell's traffic mix is built and solved
once by the program (which gives the solve's kernel tile shapes), and then
every check of ``run.py`` is read with something else in the program's
place:

* ``control``: the plain references put in the program's place and made to
  break a guarantee or lose precision. The partition comes from
  ``reference.pack_in_order`` (omega-sized blocks in id order, delta
  ignored); the kernels' outputs are the plain references summed in
  bfloat16 (the program's kernels sum in float32).
* faults planted in the program's answer: ``state_unchanged`` (the solve
  hands back its input state, every node on a block of its own),
  ``half_left_out`` (the second half of the nodes gets no block),
  ``answer_altered`` (one node moved to a new block after the program
  reported its connectivity, and one count of each kernel's output off by
  one).

Each reading prints as one JSON line; the last line says whether every
control and fault came out not correct.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import graphs  # noqa: E402
import harness  # noqa: E402
import kernels  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def kernel_control(x: dict) -> dict:
    """The plain references in bfloat16 in the kernels' place."""
    import ml_dtypes
    return kernels.reference_outputs(x, dtype=ml_dtypes.bfloat16)


def kernel_altered(out: dict) -> dict:
    """One count or sum of each kernel's outputs off by one."""
    bad = {name: {k: np.array(v, copy=True) for k, v in o.items()}
           for name, o in out.items()}
    for o in bad.values():
        for v in o.values():
            v.flat[0] += 1
    return bad


def partition_faults(g: dict, cfg: dict, rec: dict) -> dict:
    """The program's answer with each fault planted, as records."""
    n = g["n_nodes"]
    conn = lambda p: reference.audit(g, p, cfg["omega"],  # noqa: E731
                                     cfg["delta"])["connectivity"]
    ident = np.arange(n, dtype=np.int64)
    half = np.array(rec["parts"], copy=True)
    half[n // 2:] = -1
    moved = np.array(rec["parts"], copy=True)
    moved[0] = moved.max() + 1
    control = reference.pack_in_order(g, cfg["omega"])
    return dict(
        control=dict(rec, parts=control, connectivity=conn(control)),
        state_unchanged=dict(rec, parts=ident, connectivity=conn(ident)),
        half_left_out=dict(rec, parts=half),
        answer_altered=dict(rec, parts=moved))


def readings(g, cfg, rec, x, program_out) -> dict:
    """Every check's numbers for the sound program, the control and each
    planted fault."""
    sound_k = kernels.errors(x, program_out)
    out = dict(sound=run.checks([g], cfg, [rec], sound_k)["numbers"])
    faults = partition_faults(g, cfg, rec)
    kctl = kernels.errors(x, kernel_control(x))
    kalt = kernels.errors(x, kernel_altered(program_out))
    for name, bad in faults.items():
        kerrs = dict(control=kctl, answer_altered=kalt).get(name, sound_k)
        out[name] = run.checks([g], cfg, [bad], kerrs)["numbers"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    cfg = harness.config(cell["config"])
    mix = harness.traffic(cell["traffic"])
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    harness.use_program()
    from repro.core.hypergraph import Caps

    all_fail = True
    for seed in args.seeds:
        for g in graphs.requests(cfg, mix, seed):
            hg = harness.program_graph(g)
            rec = run.record(run.solve(hg, cfg), 0.0)
            x = kernels.inputs(Caps.for_host(hg), rec["kcap"], seed)
            for name, nums in readings(g, cfg, rec, x,
                                       kernels.program_outputs(x)).items():
                ok = all(v["value"] <= v["limit"] for v in nums.values())
                if name != "sound":
                    all_fail &= not ok
                print(json.dumps(dict(
                    workload=args.workload, seed=seed, n_nodes=g["n_nodes"],
                    run=name, correct=ok,
                    numbers={k: v["value"] for k, v in nums.items()})),
                    flush=True)
    print(json.dumps(dict(every_control_and_fault_not_correct=all_fail)))
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
