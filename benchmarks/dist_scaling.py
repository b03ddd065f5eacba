"""Coarsen + refine wall-time vs device count (the paper's hierarchical-
parallelism claim, Secs. V-VI, measured on forced host devices).

Each device count runs in a fresh subprocess (XLA device topology is fixed
at backend init), partitions the same SNN hypergraph through
`dist.partition` with a (1, n)-mesh Plan — all devices shard the pins/pairs
pipelines of both coarsening and refinement, and the graph *storage* is
memory-sharded (`shard_graph=True`, `dist.graph.ShardedHypergraph`) — and
reports the second run's per-phase wall-times (first run pays compile): a
coarsen-phase column, a refine-phase column, a `sort_s` column (an
events-scale distributed sample sort in isolation, with the bytes/shard the
legacy gathered sort would have moved vs the splitter sample that travels
now), and a `graph_B` column (per-device live bytes of the pins-sized
storage arrays — sharded, scaling ~1/devices — next to `graph_repl_B`, the
bytes a replicated copy pins on every device) per device count. The V-cycle
runs with `use_kernels=True`, so the stripe-local Pallas hot loops are on
the measured path; a `kernel_levels` column (`coarsen_hit/levels +
refine_hit/levels`) reports how many levels actually dispatched to them.
On this CPU
container the "devices" are host threads, so the numbers chart
overhead/scaling shape rather than real speedup; on an accelerator mesh the
same harness measures the real thing.

  PYTHONPATH=src python -m benchmarks.dist_scaling
  PYTHONPATH=src python -m benchmarks.run --only dist
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

DEVICE_COUNTS = (1, 2, 4, 8)

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + sys.argv[1])
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import generate
    from repro.core.hypergraph import Caps
    from repro.core.partitioner import partition
    from repro.dist.sharding import Plan
    from repro.utils import segops

    n_dev = int(sys.argv[1])
    mesh = jax.make_mesh((1, n_dev), ("data", "model"))
    plan = Plan.make(mesh)
    hg = generate.snn_layered(n_layers=4, width=48, fanout=8, window=12,
                              seed=2)
    res = None
    for _ in range(2):   # second run: jit cache warm per caps signature
        res = partition(hg, omega=24, delta=96, theta=4, plan=plan,
                        race=False, shard_graph=True, use_kernels=True)
    kp = res.kernel_path
    kern = "{}/{}+{}/{}".format(
        sum(1 for v in kp["coarsen"] if v), len(kp["coarsen"]),
        sum(1 for v in kp["refine"] if v), len(kp["refine"]))

    # per-device live bytes of the pins-sized storage arrays: sharded
    # stripes (the new layout) vs the replicated copy every device used to
    # pin — the ~1/devices memory claim of the sharded storage
    from repro.dist import graph as dist_graph
    caps0 = Caps.for_host(hg)
    g = dist_graph.sharded_from_host(hg, caps0, plan)
    graph_B = g.pins_bytes_per_device()
    graph_repl_B = sum(
        np.dtype(dt).itemsize * caps0.p
        for dt in (np.int32, np.int32, np.bool_))  # pins/edges/is_in

    # events-scale distributed sort in isolation (PR 4): wall time plus the
    # bytes/shard the legacy gathered sort would have all-gathered vs the
    # splitter sample that now travels instead
    caps = Caps.for_host(hg)
    per = -(-caps.p // n_dev)
    L = 2 * per * n_dev            # inbound-events pipeline length
    ctx = (segops.ShardCtx(axis="model", nshards=n_dev) if n_dev > 1
           else segops.ShardCtx())
    rng = np.random.default_rng(0)
    ka = jnp.asarray(rng.integers(0, 8, L).astype(np.int32))
    kb = jnp.asarray(rng.integers(0, max(hg.n_edges, 1), L).astype(np.int32))
    ks = jnp.asarray(rng.permutation(L).astype(np.int32))
    pv = jnp.arange(L, dtype=jnp.int32)

    def body(a, b, c, p):
        ks_, ps_ = ctx.sort_by(
            [ctx.stripe(a), ctx.stripe(b), ctx.stripe(c)], [ctx.stripe(p)],
            striped_in=True, striped_out=True)
        return (*ks_, *ps_)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),) * 4,
                              out_specs=(P("model"),) * 4, check_vma=False))
    jax.block_until_ready(f(ka, kb, ks, pv))
    t0 = time.perf_counter()
    jax.block_until_ready(f(ka, kb, ks, pv))
    sort_s = time.perf_counter() - t0
    q = max(1, min(per * 2, 4 * n_dev))
    print(json.dumps(dict(refine_s=res.timings["refine"],
                          coarsen_s=res.timings["coarsen"],
                          total_s=res.timings["total"],
                          sort_s=sort_s,
                          sort_gather_B=int(L) * 4 * 4,
                          sort_splitter_B=n_dev * q * 4 * 4,
                          graph_B=int(graph_B),
                          graph_repl_B=int(graph_repl_B),
                          kernel_levels=kern,
                          connectivity=res.connectivity,
                          n_parts=res.n_parts)))
""")


def run() -> list[str]:
    from benchmarks.common import row

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    # forced host devices: the children never open an accelerator
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out, base = [], None
    for n in DEVICE_COUNTS:
        try:
            r = subprocess.run([sys.executable, "-c", _CHILD, str(n)],
                               env=env, capture_output=True, text=True,
                               timeout=1800)
        except subprocess.TimeoutExpired:
            out.append(row(f"dist_scaling/dev{n}", 0.0, "ERROR: timeout"))
            continue
        if r.returncode != 0:
            err = (r.stderr.strip().splitlines() or ["no stderr"])[-1]
            out.append(row(f"dist_scaling/dev{n}", 0.0,
                           f"ERROR: {err[:120]}"))
            continue
        m = json.loads(r.stdout.strip().splitlines()[-1])
        # rel_dev1 only once the dev-1 baseline itself succeeded
        if n == DEVICE_COUNTS[0]:
            base = m["refine_s"]
        rel = (f"rel_dev{DEVICE_COUNTS[0]}={m['refine_s'] / base:.2f}x"
               if base else "rel_dev1=n/a")
        out.append(row(
            f"dist_scaling/dev{n}", m["refine_s"] * 1e6,
            f"coarsen_s={m['coarsen_s']:.3f} refine_s={m['refine_s']:.3f} "
            f"sort_s={m['sort_s']:.4f} total_s={m['total_s']:.3f} "
            f"sort_gather_B={m['sort_gather_B']} "
            f"sort_splitter_B={m['sort_splitter_B']} "
            f"graph_B={m['graph_B']} graph_repl_B={m['graph_repl_B']} "
            f"kernel_levels={m['kernel_levels']} "
            f"conn={m['connectivity']:.0f} {rel}"))
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)
