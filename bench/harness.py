"""Shared plumbing of the benchmark: where things are, how a cell's
configuration, traffic mix and per-layer metric readers are found by name,
the compile cache, the chip check and the compile counters.

Everything a later PR adds (a configuration, a traffic mix, a metric) is a
file found here by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` (the
parameters that ``graphs.requests`` reads) and ``bench/metrics/<metric>.py``
(a module with ``read(run) -> float | None``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, root: Path = ROOT) -> dict:
    cells = {w["name"]: w for w in benchmark(root)["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    return cells[name]


def config(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "configs" / f"{name}.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    """A traffic mix: ``scales``, the sizes of the graphs the window solves
    in turn, as fractions of the configuration's neuron count."""
    mix = load_json(bench / "traffic" / f"{name}.json")
    scales = mix.get("scales")
    if not (isinstance(scales, list) and scales
            and all(isinstance(s, (int, float)) and s > 0 for s in scales)):
        raise SystemExit(f"traffic {name!r}: 'scales' must be a list of "
                         f"positive numbers, not {scales!r}")
    return mix


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(cell: str, root: Path = ROOT) -> list[dict]:
    """The per-layer metrics that ``cell`` reports."""
    return [m for m in benchmark(root)["per_layer"]
            if cell in m.get("workloads", [cell])]


def use_program() -> None:
    """Make the program under test importable (it lives in ``src/``)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def program_graph(g: dict):
    """The benchmark's plain graph as the program's input type."""
    use_program()
    from repro.core.hypergraph import HostHypergraph
    return HostHypergraph(n_nodes=g["n_nodes"], edge_off=g["edge_off"],
                          edge_pins=g["edge_pins"], edge_nsrc=g["edge_nsrc"],
                          edge_w=g["edge_w"])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, whatever the environment says, so that only a cell's first
    run in a checkout compiles. Every program is cached, however fast it
    compiled."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chips(n_chips: int):
    """The TPU devices, or exit non-zero before anything is measured."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX backend {devs[0].platform!r}); "
                         f"nothing was measured")
    if len(devs) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX sees "
                         f"{len(devs)}; nothing was measured")
    return devs


class CompileCounter:
    """Counts XLA compiles (a backend compile or a fetch from the
    persistent cache) and persistent-cache hits and misses through
    ``jax.monitoring``, per phase (``phase`` is set by the caller)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.phase = "setup"
        self.counts: dict = {}
        self._lock = threading.Lock()

    def _add(self, key, value):
        with self._lock:
            self.counts[key] = self.counts.get(key, 0) + value

    def _on_event(self, event, **_):
        if event in (self.HIT, self.MISS):
            self._add((self.phase, event.rsplit("/", 1)[1]), 1)

    def _on_duration(self, event, duration, **_):
        if event in (self.COMPILE, self.TRACE, self.LOWER):
            name = event.rsplit("/", 1)[1]
            self._add((self.phase, name), duration)
            self._add((self.phase, name + "_count"), 1)

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def get(self, phase: str, name: str, default=0):
        with self._lock:
            return self.counts.get((phase, name), default)


class HostClock:
    """What the host did while a solve ran, to tell a slow solve's cause:
    seconds in Python's garbage collector (per generation), the process's
    CPU seconds, its involuntary context switches (the process preempted
    on a shared host) and major page faults (the process waiting on a
    page read from disk), and the CPU seconds the hypervisor stole from
    the machine, summed over its cores (the machine stood still)."""

    def __init__(self):
        self.gc_s = [0.0, 0.0, 0.0]
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.gc_s[info["generation"]] += time.perf_counter() - self._t0
            self._t0 = None

    @staticmethod
    def _steal_s() -> float:
        try:
            with open("/proc/stat") as f:
                steal = int(f.readline().split()[8])
        except (OSError, IndexError, ValueError):
            return 0.0
        return steal / os.sysconf("SC_CLK_TCK")

    def read(self) -> tuple:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (*self.gc_s, ru.ru_utime + ru.ru_stime, ru.ru_nivcsw,
                ru.ru_majflt, self._steal_s())

    @staticmethod
    def since(before: tuple, after: tuple) -> dict:
        d = [b - a for a, b in zip(before, after)]
        return dict(gc_s=d[:3], cpu_s=d[3], preempted=d[4], major_faults=d[5],
                    steal_s=d[6])
