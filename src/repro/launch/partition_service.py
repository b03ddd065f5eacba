"""Multi-tenant partition service launcher CLI.

  PYTHONPATH=src python -m repro.launch.partition_service --requests 16 \
      --nodes 48 --edges 64 --pins 4 --omega 16 --delta 256 [--mixed] \
      [--mesh host --replicas 2] [--route-threshold 2048] [--json out.json]

Feeds a flood of generated requests through `serve.PartitionService`:
small/medium graphs batch into capacity buckets (one vmapped device solve
per bucket batch), anything above --route-threshold takes the host-driven
V-cycle — mesh-sharded when --mesh host (force a multi-device CPU run with
XLA_FLAGS=--xla_force_host_platform_device_count=8). --mixed interleaves a
few over-threshold graphs into the flood to exercise both lanes.

--metrics-json PATH dumps the full telemetry document (metric registry
snapshot + aggregated span tree — see docs/observability.md) on exit;
--metrics-interval N additionally rewrites it every N seconds while the
flood drains (a `PeriodicDumper` thread).
"""
from __future__ import annotations

import argparse
import json
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--nodes", type=int, default=48)
    ap.add_argument("--edges", type=int, default=64)
    ap.add_argument("--pins", type=int, default=4,
                    help="pins per hyperedge of the generated requests")
    ap.add_argument("--omega", type=int, default=16)
    ap.add_argument("--delta", type=int, default=256)
    ap.add_argument("--theta", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--bucket-base", type=int, default=64)
    ap.add_argument("--route-threshold", type=int, default=2048)
    ap.add_argument("--deadline", type=float, default=300.0,
                    help="per-solve StepWatchdog deadline (s)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--mixed", action="store_true",
                    help="make every 4th request over-threshold so the "
                         "routed V-cycle lane runs too")
    ap.add_argument("--mesh", choices=["none", "host"], default="none")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--no-race", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", default=None)
    ap.add_argument("--metrics-json", default=None,
                    help="write the telemetry dump (registry snapshot + "
                         "span aggregate) to this path on exit")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="also rewrite --metrics-json every N seconds "
                         "while running (0: only the final dump)")
    ap.add_argument("--collect-stats", action="store_true",
                    help="collect per-level LevelStats on the routed lane")
    args = ap.parse_args(argv)

    from repro import obs
    from repro.core.generate import random_kuniform
    from repro.launch.partition import build_plan
    from repro.serve import PartitionService
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    plan = build_plan(args.replicas) if args.mesh == "host" else None
    # the CLI joins the process-global registry so one --metrics-json dump
    # carries service + span + watchdog series together
    svc = PartitionService(
        theta=args.theta, batch_slots=args.batch_slots,
        bucket_base=args.bucket_base, route_threshold=args.route_threshold,
        plan=plan, race=not args.no_race, deadline_s=args.deadline,
        max_restarts=args.max_restarts, registry=obs.metrics.REGISTRY,
        collect_stats=args.collect_stats)
    dumper = None
    if args.metrics_json and args.metrics_interval > 0:
        dumper = obs.PeriodicDumper(args.metrics_json,
                                    args.metrics_interval)

    reqs = []
    for i in range(args.requests):
        if args.mixed and i % 4 == 3:
            n = 2 * args.route_threshold
            hg = random_kuniform(n, 2 * n, args.pins, seed=args.seed + i)
            reqs.append((hg, max(args.omega, n // 8), args.delta * 4))
        else:
            hg = random_kuniform(args.nodes, args.edges, args.pins,
                                 seed=args.seed + i)
            reqs.append((hg, args.omega, args.delta))

    t0 = time.perf_counter()
    rids = [svc.submit(hg, omega=o, delta=d) for hg, o, d in reqs]
    res = svc.drain()
    wall = time.perf_counter() - t0
    svc.close()
    if dumper is not None:
        dumper.stop()          # writes the final dump
    elif args.metrics_json:
        from repro.obs.metrics import dump_json
        dump_json(args.metrics_json)

    assert sorted(res) == sorted(rids), "lost rids"
    routes: dict[str, int] = {}
    for r in res.values():
        routes[r.route] = routes.get(r.route, 0) + 1
    out = dict(
        requests=args.requests, wall_s=wall,
        req_per_s=args.requests / wall, routes=routes,
        all_size_ok=all(r.audit["size_ok"] for r in res.values()),
        all_inbound_ok=all(r.audit["inbound_ok"] for r in res.values()),
        mean_connectivity=sum(r.connectivity for r in res.values())
        / len(res),
        mean_queue_wait_s=sum(r.queue_wait_s for r in res.values())
        / len(res),
        mean_solve_s=sum(r.solve_s for r in res.values()) / len(res),
        stats=svc.stats,
        mesh=(dict(plan.mesh.shape) if plan is not None else None),
    )
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    main()
