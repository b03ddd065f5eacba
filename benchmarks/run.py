"""Benchmark driver: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig7,fig8,...] \
      [--smoke] [--metrics-json out.json]

--metrics-json captures one telemetry document per lane: the global metric
registry and span tree are reset before each lane and snapshotted after it,
so the written ``{"lanes": {name: {ts, metrics, spans}}}`` attributes every
series to the lane that produced it (the per-lane documents are the same
shape ``--metrics-json`` CLIs write; tests/data/metrics_schema.json pins
it). --smoke sets REPRO_BENCH_SMOKE=1 for lanes that honor it (CI runs
``--only partition_service --smoke`` as its metrics-smoke step).
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig7,fig8,fig15,fig16,tab2,roofline,"
                         "proofline,dist,dist_sort,serve_engine,"
                         "partition_service,repartition")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink lanes that honor REPRO_BENCH_SMOKE "
                         "(CI metrics-smoke mode)")
    ap.add_argument("--metrics-json", default=None,
                    help="write per-lane telemetry snapshots to this path")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from benchmarks import (dist_scaling, dist_sort, fig7_snn_comparison,
                            fig8_breakdown, fig15_kway, fig16_ablations,
                            partition_service, partitioner_roofline,
                            repartition, roofline, serve_engine,
                            tab2_work_span)
    mods = {
        "fig7": fig7_snn_comparison,
        "fig8": fig8_breakdown,
        "fig15": fig15_kway,
        "fig16": fig16_ablations,
        "tab2": tab2_work_span,
        "roofline": roofline,
        "proofline": partitioner_roofline,
        "dist": dist_scaling,
        "dist_sort": dist_sort,
        "serve_engine": serve_engine,
        "partition_service": partition_service,
        "repartition": repartition,
    }
    want = args.only.split(",") if args.only else list(mods)
    lanes: dict = {}
    if args.metrics_json:
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
    print("name,us_per_call,derived")
    failed = []
    for key in want:
        if args.metrics_json:
            # reset the global registry + span tree so the lane's snapshot
            # attributes every series to this lane alone
            obs_metrics.REGISTRY.reset()
            obs_trace.reset()
        t0 = time.time()
        try:
            for line in mods[key].run():
                print(line, flush=True)
        except Exception as e:  # report the row, run the other lanes
            print(f"{key}/ERROR,0.0,{type(e).__name__}: {e}", flush=True)
            failed.append(key)
        print(f"{key}/_elapsed,{(time.time()-t0)*1e6:.0f},", flush=True)
        if args.metrics_json:
            lanes[key] = dict(ts=time.time(),
                              metrics=obs_metrics.REGISTRY.snapshot(),
                              spans=obs_trace.aggregate())
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(dict(lanes=lanes), f, indent=2, sort_keys=True)
    if failed:
        raise SystemExit(f"lanes failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
