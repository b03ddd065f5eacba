#!/usr/bin/env python3
"""The on-chip benchmark of the constrained partitioner.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``: the graph, omega, delta, theta and the
limits of its checks) and a traffic mix (``bench/traffic/<mix>.json``: the
sizes of the graphs the window solves in turn). One run:

1. exits non-zero, printing no result, unless JAX runs on a TPU with as
   many chips as the cell asks for;
2. builds the mix's graphs with the benchmark's own generator, their
   hyperedges in an order drawn from ``--seed`` (``graphs.requests``);
3. set-up: one solve of each graph through
   ``repro.core.partitioner.partition(..., use_kernels=True)``, which
   compiles every program the window uses (JAX's persistent cache lives in
   ``bench/.jax_cache``);
4. the window: the graphs solved in turn, back to back, each solve waiting
   for the last, until ``--seconds`` have passed and the last one has
   finished; with ``--trace 1`` under the profiler;
5. the checks: every solve's parts against the plain audit of
   ``reference.py``, and the program's three Pallas kernels at each
   graph's tile shapes against their plain references (``kernels.py``);
6. prints each number checked beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard
   output: end-to-end metrics with ``--trace 0``, the per-layer metrics of
   ``bench/metrics/<name>.py`` with ``--trace 1``.

``--n-nodes`` overrides the configuration's neuron count, for sizing a
configuration; the benchmark's own runs never pass it.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import graphs  # noqa: E402
import harness  # noqa: E402
import kernels  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402

TRACE_DIR = harness.BENCH / ".trace"
SPAN_NAMES = {"partition", "setup", "coarsen", "coarsen_level", "refine",
              "refine_level", "audit", tracing.WINDOW}


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets (``bench/metrics/<name>.py``)."""

    cell: dict
    config: dict
    solves: list          # one dict per window solve (see ``record``)
    window_s: float
    counter: harness.CompileCounter
    device_kind: str
    trace: dict | None = None


def solve(hg, cfg: dict):
    """The entry the window drives; returns with the parts on the host."""
    from repro.core.partitioner import partition
    return partition(hg, cfg["omega"], cfg["delta"], theta=cfg["theta"],
                     use_kernels=True)


def record(res, wall: float, graph: int = 0) -> dict:
    """The solve's graph (its index in the mix), parts, reported
    connectivity, phase spans and levels (pins, nodes and gains-kernel
    repetitions per level, finest first)."""
    from repro.obs import trace as otrace
    root = otrace.last_root("partition")
    k = res.level_stats[-1].nodes
    return dict(graph=graph, wall=wall, parts=res.parts,
                connectivity=res.connectivity,
                spans={c.name: c.duration for c in root.children},
                levels=[(ls.pins, ls.nodes, ls.kernel_refine or 0)
                        for ls in res.level_stats],
                kcap=1 << max(0, math.ceil(math.log2(max(1, k)))))


def solve_numbers(g: dict, cfg: dict, rec: dict) -> tuple[dict, dict]:
    """One solve's numbers for the checks that the configuration gives a
    limit (an unreadable answer reads infinite), and the plain audit they
    came from."""
    a = reference.audit(g, rec["parts"], cfg["omega"], cfg["delta"])
    gap = abs(rec["connectivity"] - a["connectivity"])
    finite = lambda v: v if math.isfinite(v) else math.inf  # noqa: E731
    nums = dict(unassigned=a["unassigned"], max_block=a["max_block"],
                max_inbound=a["max_inbound"], conn_gap=finite(gap),
                cut_share=finite(a["cut_share"]),
                block_share=a["n_blocks"] / g["n_nodes"])
    return {k: v for k, v in nums.items() if k in cfg["limits"]}, a


def checks(gs: list, cfg: dict, recs: list, kernel_errs: dict) -> dict:
    """Each number compared, the worst over the solves (each audited
    against its own graph of ``gs``), with its limit; how many solves
    failed; the audits."""
    per, audits = zip(*(solve_numbers(gs[r["graph"]], cfg, r)
                        for r in recs))
    nums = {k: max(p[k] for p in per) for k in per[0]} | kernel_errs
    return dict(
        numbers={k: dict(value=v, limit=cfg["limits"][k])
                 for k, v in nums.items()},
        failed=sum(not _within(p, cfg["limits"]) for p in per),
        audits=list(audits))


def _within(nums: dict, limits: dict) -> bool:
    return all(v <= limits[k] for k, v in nums.items())


def kernel_errors(hgs: list, recs: list, seed: int) -> dict:
    """The worst gap of each kernel over the tile shapes of every graph
    (with the partition count of its last solve in ``recs``)."""
    from repro.core.hypergraph import Caps
    worst = {}
    for i, hg in enumerate(hgs):
        kcap = [r for r in recs if r["graph"] == i][-1]["kcap"]
        x = kernels.inputs(Caps.for_host(hg), kcap, seed)
        for k, v in kernels.errors(x, kernels.program_outputs(x)).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--n-nodes", type=int, default=None,
                    help="override the configuration's neuron count "
                         "(sizing only)")
    args = ap.parse_args(argv)

    cell = harness.workload(args.workload)
    cfg = harness.config(cell["config"])
    mix = harness.traffic(cell["traffic"])
    devs = harness.require_chips(cell["chips"])
    import jax
    harness.enable_compile_cache()
    counter = harness.CompileCounter().install()
    harness.use_program()
    gs = graphs.requests(cfg, mix, args.seed, args.n_nodes)
    hgs = [harness.program_graph(g) for g in gs]

    # set-up: a solve of each graph compiles (or loads) every program
    recs = []
    for i, hg in enumerate(hgs):
        t = time.perf_counter()
        recs.append(record(solve(hg, cfg), time.perf_counter() - t, i))
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f}s; graphs of "
          f"{', '.join(str(g['n_nodes']) for g in gs)} neurons; compile "
          f"cache hits={counter.get('setup', 'cache_hits')} "
          f"misses={counter.get('setup', 'cache_misses')}; "
          f"compiles={counter.get('setup', 'backend_compile_duration_count')}"
          f" in {counter.get('setup', 'backend_compile_duration'):.3f}s; "
          f"tracing {counter.get('setup', 'jaxpr_trace_duration'):.3f}s; "
          f"lowering {counter.get('setup', 'jaxpr_to_mlir_module_duration'):.3f}s",
          flush=True)

    # the window
    counter.phase = "window"
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=profiler_options())
    window = []
    host = harness.HostClock()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t_w0 = time.perf_counter()
        while True:
            i = len(window) % len(hgs)
            h, t = host.read(), time.perf_counter()
            res = solve(hgs[i], cfg)
            window.append(record(res, time.perf_counter() - t, i)
                          | dict(host=host.since(h, host.read())))
            if time.perf_counter() - t_w0 >= args.seconds:
                break
        window_s = time.perf_counter() - t_w0
    if args.trace:
        jax.profiler.stop_trace()
    counter.phase = "check"
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    print(f"window: {len(window)} solves in {window_s:.3f}s "
          f"({', '.join(f'{r['wall']:.3f}' for r in window)}); compiles="
          f"{counter.get('window', 'backend_compile_duration_count')}",
          flush=True)
    print("host per window solve: gc s by generation ("
          + "; ".join(" ".join(f"{g:.3f}" for g in r["host"]["gc_s"])
                      for r in window)
          + "), cpu s (" + ", ".join(f"{r['host']['cpu_s']:.3f}"
                                      for r in window)
          + "), preempted (" + ", ".join(str(r["host"]["preempted"])
                                         for r in window)
          + "), major faults (" + ", ".join(str(r["host"]["major_faults"])
                                            for r in window)
          + "), steal s (" + ", ".join(f"{r['host']['steal_s']:.2f}"
                                       for r in window) + ")", flush=True)

    # the checks, once the window has closed
    kernel_errs = kernel_errors(hgs, recs + window, args.seed)
    chk = checks(gs, cfg, recs + window, kernel_errs)
    numbers = chk["numbers"]
    correct = _within({k: v["value"] for k, v in numbers.items()},
                      {k: v["limit"] for k, v in numbers.items()})

    dev = devs[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()), memory_peak_bytes=int(peak))
    result = dict(correct=bool(correct), attempted=len(recs + window),
                  failed=chk["failed"])
    if args.trace:
        trace = tracing.from_xplane(str(TRACE_DIR), SPAN_NAMES)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run = Run(cell=cell, config=cfg, solves=window, window_s=window_s,
                  counter=counter, device_kind=dev.device_kind, trace=trace)
        metrics = {}
        for m in harness.per_layer_metrics(cell["name"]):
            value = harness.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        t0, t1 = tracing.window_bounds(trace)
        device.update(busy_s=tracing.busy_s(trace), window_s=(t1 - t0) / 1e9)
        result.update(metrics=metrics, device=device,
                      breakdown=dict(device_ops=tracing.top_ops(trace),
                                     idle_gaps=tracing.idle_gaps(trace)))
    else:
        last = chk["audits"][-1]
        result.update(metrics=dict(
            setup_s=dict(value=setup_s, unit="s"),
            solve_s=dict(value=window_s / len(window), unit="s"),
            connectivity=dict(value=last["connectivity"], unit="weight"),
            parts=dict(value=last["n_blocks"], unit="cores")),
            device=device)
    result["checks"] = numbers
    for k in sorted(cfg["limits"].keys() - numbers.keys()):
        print(f"check {k}: not read", file=sys.stderr)
    for k, v in numbers.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
