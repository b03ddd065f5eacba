"""Roofline terms for the partitioner's own level-step programs (the
paper's Fig. 11 analogue, derived from compiled HLO instead of measured
counters): lower + compile coarsen_step / refine_step, walk the HLO with
trip correction, report compute vs memory terms against the peaks of the
device the programs were compiled for (rows are skipped on a device with
no entry in ``repro.launch.dryrun.PEAKS``, such as the CPU).

Two lanes ride along with the HLO rows:
  * kernel-path coverage — runs a small V-cycle with ``use_kernels=True``
    and reports, per phase, how many levels actually dispatched to the
    Pallas kernels (``PartitionResult.kernel_path``). A roofline for
    kernels that never fire is fiction; this row keeps the dispatch
    honest.
  * TPU-mesh lane — on a TPU backend, times the same V-cycle under a
    ``Plan`` over all local devices with the kernels *compiled*
    (``pallas_interpret()`` is False there). On the CPU backend the row is
    emitted as ``skipped`` so CSV consumers see a stable schema.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, timed
from repro.core import generate
from repro.core import hypergraph as H
from repro.core import refine as R
from repro.core.coarsen import CoarsenParams, coarsen_step
from repro.launch import hlo_cost
from repro.launch.dryrun import PEAKS

def _coverage(kernel_path: dict) -> str:
    c, r = kernel_path["coarsen"], kernel_path["refine"]
    return (f"coarsen_kernel_levels={sum(1 for v in c if v)}/{len(c)} "
            f"refine_kernel_levels={sum(1 for v in r if v)}/{len(r)}")


def kernel_coverage_rows(hg, omega: int, delta: int) -> list[str]:
    """Per-level kernel-path coverage for a kernels-on V-cycle."""
    from repro.core.partitioner import partition

    res, dt = timed(partition, hg, omega=omega, delta=delta, theta=2,
                    use_kernels=True)
    return [row("partitioner_roofline/kernel_coverage", dt * 1e6,
                _coverage(res.kernel_path))]


def tpu_mesh_rows(hg, omega: int, delta: int) -> list[str]:
    """Kernels-on V-cycle on a device mesh, compiled Pallas — TPU only
    (the CPU backend has no compiled Pallas path)."""
    backend = jax.default_backend()
    if backend != "tpu":
        return [row("partitioner_roofline/tpu_mesh", 0.0,
                    f"skipped backend={backend}")]
    from repro.core.partitioner import partition
    from repro.dist.sharding import Plan

    n_dev = len(jax.devices())
    plan = Plan.make(jax.make_mesh((1, n_dev), ("data", "model")))
    kw = dict(omega=omega, delta=delta, theta=2, use_kernels=True,
              plan=plan, race=False)
    timed(partition, hg, **kw)  # warm the compile caches
    res, dt = timed(partition, hg, **kw)
    return [row("partitioner_roofline/tpu_mesh", dt * 1e6,
                f"backend={backend} devices={n_dev} "
                + _coverage(res.kernel_path))]


def _terms(lowered_compiled, pk: dict) -> dict:
    w = hlo_cost.analyze(lowered_compiled.as_text())
    return dict(compute_s=w["flops"] / pk["flops"],
                memory_s=w["bytes"] / pk["hbm_bw"],
                flops=w["flops"], bytes=w["bytes"])


def _roofline_row(name: str, compiled, pk: dict) -> str:
    t = _terms(compiled, pk)
    dom = "memory" if t["memory_s"] > t["compute_s"] else "compute"
    return row(name, max(t["compute_s"], t["memory_s"]) * 1e6,
               f"compute_ms={t['compute_s']*1e3:.3f} "
               f"mem_ms={t['memory_s']*1e3:.3f} bound={dom}")


def run() -> list[str]:
    out = []
    kind = jax.devices()[0].device_kind
    pk = PEAKS.get(kind)
    names = ("partitioner_roofline/coarsen_step",
             "partitioner_roofline/refine_step")
    if pk is None:
        out += [row(nm, 0.0, f"skipped: no peaks for device kind {kind!r}")
                for nm in names]
    else:
        hg = generate.snn_smallworld(n_nodes=768, fanout=12, seed=5)
        caps = H.Caps.for_host(hg)
        d = H.device_from_host(hg, caps)
        cp = CoarsenParams(omega=48, delta=192)
        out.append(_roofline_row(
            names[0], coarsen_step.lower(d, caps, cp).compile(), pk))
        parts = jnp.arange(caps.n, dtype=jnp.int32) % 24
        rp = R.RefineParams(omega=48, delta=192, theta=1)
        out.append(_roofline_row(
            names[1], R.refine_step.lower(d, parts, jnp.int32(24), caps, 32,
                                          rp, True).compile(), pk))

    hg_small = generate.snn_smallworld(n_nodes=192, fanout=8, seed=5)
    out += kernel_coverage_rows(hg_small, omega=24, delta=96)
    out += tpu_mesh_rows(hg_small, omega=24, delta=96)
    return out
