"""Partitioner launcher CLI (single-device or mesh-sharded refinement).

  PYTHONPATH=src python -m repro.launch.partition --graph snn --nodes 400 \
      --omega 32 --delta 128 --theta 8 [--mesh host --replicas 2] \
      [--no-race] [--json out.json]

--mesh none runs the classic single-device `core.partitioner.partition`;
--mesh host builds a (replicas, n_local_devices // replicas) Plan over the
locally visible devices and routes the whole V-cycle on-mesh: coarsening
through `dist.partition.coarsen_level`/`contract_level` (sharded pairs/pins
pipelines over "model"; `--single-coarsen` keeps coarsening on one device)
and refinement through `dist.partition.refine_level` (replica racing over
"data", sharded pins pipelines over "model"). `--repartition-from prev.json`
warm-starts from an earlier run's `--json` dump (refine-only, no
coarsening; `--perturb-edges N` applies a synthetic incremental delta
first, and drift / audit failures fall back to a cold V-cycle
automatically). `--shard-graph` additionally
memory-shards the graph *storage* (pins-sized arrays as per-shard stripes
over "model", shared by the racing replicas — `dist.graph`). Force a
multi-device CPU run with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
from __future__ import annotations

import argparse
import json


def build_plan(replicas: int):
    import jax
    from repro.dist.sharding import Plan

    n = len(jax.devices())
    r = max(1, min(replicas, n))
    mesh = jax.make_mesh((r, n // r), ("data", "model"))
    return Plan.make(mesh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", choices=["snn", "smallworld", "ispd"],
                    default="snn")
    ap.add_argument("--nodes", type=int, default=400)
    ap.add_argument("--omega", type=int, default=32)
    ap.add_argument("--delta", type=int, default=128)
    ap.add_argument("--theta", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mesh", choices=["none", "host"], default="none")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-axis size (racing repetitions) of the host "
                         "mesh; remaining devices shard the pipelines")
    ap.add_argument("--no-race", action="store_true",
                    help="identity tie-breaks on every replica "
                         "(deterministic parity mode)")
    ap.add_argument("--single-coarsen", action="store_true",
                    help="keep coarsening single-device (refinement still "
                         "runs on the mesh)")
    ap.add_argument("--shard-graph", action="store_true",
                    help="memory-shard the graph storage: pins-sized arrays "
                         "live as per-shard stripes over the mesh's model "
                         "axis (racing replicas share the one sharded copy); "
                         "bit-identical results, O(pins/shards) storage per "
                         "device (requires --mesh host)")
    ap.add_argument("--compensated-psum", action="store_true",
                    help="combine the coarsening eta / matching-sum0 float "
                         "reductions with the Neumaier-compensated psum "
                         "(O(dense) traffic, ~1 ulp; drops bit-exact parity "
                         "with the single-device run)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="dispatch the pair-scores / gains / pins-count hot "
                         "loops through the Pallas kernels where the "
                         "fits_kernel bounds allow (stripe-local under a "
                         "mesh); the per-level outcome is reported as "
                         "kernel_path in the output")
    ap.add_argument("--race-seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--repartition-from", default=None, metavar="PREV.json",
                    help="warm-start from a previous --json dump: skip "
                         "coarsening and re-refine from its parts vector "
                         "(core.partitioner.repartition; drift/audit "
                         "fallbacks to a cold V-cycle are automatic). The "
                         "dump must come from the same --graph/--nodes/"
                         "--seed so the parts align")
    ap.add_argument("--perturb-edges", type=int, default=0, metavar="N",
                    help="apply a synthetic GraphDelta before solving: "
                         "delete N random h-edges and insert N fresh "
                         "similar-shaped ones (generate.perturb_delta; "
                         "deterministic in --perturb-seed)")
    ap.add_argument("--perturb-seed", type=int, default=0)
    ap.add_argument("--drift-threshold", type=float, default=0.25,
                    help="fraction of pins touched by deltas above which a "
                         "warm --repartition-from solve falls back to the "
                         "cold V-cycle")
    args = ap.parse_args(argv)

    from repro.core import generate
    from repro.core.partitioner import partition, repartition
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.graph == "snn":
        hg = generate.snn_layered(n_layers=5, width=max(args.nodes // 5, 4),
                                  fanout=10, seed=args.seed)
    elif args.graph == "smallworld":
        hg = generate.snn_smallworld(n_nodes=args.nodes, fanout=10,
                                     seed=args.seed)
    else:
        hg = generate.ispd_like(n_nodes=args.nodes, seed=args.seed)
    print("hypergraph:", hg.stats())

    plan = build_plan(args.replicas) if args.mesh == "host" else None
    if args.shard_graph and plan is None:
        raise SystemExit("--shard-graph requires --mesh host (graph stripes "
                         "live on the mesh's model axis)")

    deltas = []
    if args.perturb_edges > 0:
        deltas.append(generate.perturb_delta(hg, n_edges=args.perturb_edges,
                                             seed=args.perturb_seed))
    common = dict(theta=args.theta, plan=plan, race=not args.no_race,
                  race_seed=args.race_seed,
                  dist_coarsen=not args.single_coarsen,
                  compensated_psum=args.compensated_psum,
                  shard_graph=args.shard_graph,
                  use_kernels=args.use_kernels)
    if args.repartition_from:
        with open(args.repartition_from) as f:
            prev = json.load(f)
        if prev.get("parts") is None:
            raise SystemExit(f"{args.repartition_from} carries no parts "
                             "vector (written by an older run?)")
        if len(prev["parts"]) != hg.n_nodes:
            raise SystemExit(
                f"previous parts vector has {len(prev['parts'])} entries "
                f"for {hg.n_nodes} nodes — same --graph/--nodes/--seed?")
        res = repartition(hg, prev["parts"], args.omega, args.delta,
                          deltas=deltas,
                          drift_threshold=args.drift_threshold, **common)
        print(f"repartition mode={res.mode} "
              f"(warm refine {res.timings['refine']:.3f}s, "
              f"total {res.timings['total']:.3f}s vs previous total "
              f"{prev.get('timings', {}).get('total', float('nan')):.3f}s)")
    else:
        for dl in deltas:
            from repro.core.hypergraph import apply_delta
            apply_delta(hg, dl)
        res = partition(hg, omega=args.omega, delta=args.delta, **common)
    out = dict(
        connectivity=res.connectivity, cut_net=res.cut_net,
        n_parts=res.n_parts, n_levels=res.n_levels,
        size_ok=bool(res.audit["size_ok"]),
        inbound_ok=bool(res.audit["inbound_ok"]),
        timings=res.timings,
        mode=res.mode,
        parts=[int(p) for p in res.parts],
        kernel_path=res.kernel_path if args.use_kernels else None,
        mesh=(dict(plan.mesh.shape) if plan is not None else None),
        race=(not args.no_race) if plan is not None else None,
        dist_coarsen=(not args.single_coarsen) if plan is not None else None,
        shard_graph=args.shard_graph if plan is not None else None,
    )
    # stdout skips the parts vector (noise at scale); the --json dump keeps
    # it — that is what --repartition-from reloads
    print(json.dumps({k: v for k, v in out.items() if k != "parts"},
                     indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
