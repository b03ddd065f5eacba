"""k-way balanced partitioning mode (paper Sec. VII-E).

Minimal changes from the constrained mode, as in the paper:
  Omega = (1+eps) * |N| / k,  Delta = +inf,
coarsening halts early (paper: < 4096 coarse nodes, empirically stable for
small k) and a robust initial k-way partitioning is computed on the coarse
graph. The paper delegates that step to Mt-KaHyPar's direct k-way mode
(tens of ms on CPU, included in timings); offline we implement a greedy
affinity + least-load placement on the (tiny) coarsest graph instead, a
deviation from the paper. Uncoarsening + refinement then run
exactly as in the constrained mode with K = k.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.core.coarsen import CoarsenParams
from repro.core.hypergraph import (Caps, HostHypergraph,
                                   check_expansion_caps, device_from_host,
                                   device_pair_count, host_from_device,
                                   host_pair_count)
from repro.core.partitioner import (PartitionResult, _next_pow2,
                                    make_coarsen_fns, make_refine_fn,
                                    run_coarsen_loop, run_refine_loop)
from repro.core.refine import RefineParams
from repro.obs import trace as otrace
from repro.obs import vcycle as ovcycle

BIG_DELTA = 2 ** 29


def greedy_initial_kway(hg: HostHypergraph, node_size: np.ndarray, k: int,
                        omega: int) -> np.ndarray:
    """Greedy affinity placement on the coarsest graph (host-side; the
    coarsest graph is tiny). Nodes in size-descending order pick the
    partition with the highest total weight of h-edges already touching it,
    subject to the size budget; ties -> least-loaded, then lowest id."""
    N = hg.n_nodes
    parts = np.full(N, -1, np.int64)
    load = np.zeros(k, np.int64)
    affinity = np.zeros((N, k), np.float64)
    node_off, node_edges, _, _ = hg.incidence()
    order = np.lexsort((np.arange(N), -node_size[:N]))
    edge_pin_cache = [hg.edge(e) for e in range(hg.n_edges)]
    for n in order:
        fits = load + node_size[n] <= omega
        if not fits.any():
            fits = load == load.min()  # relief valve: least-loaded
        cand = np.where(fits)[0]
        best = cand[np.lexsort((cand, load[cand], -affinity[n, cand]))[0]]
        parts[n] = best
        load[best] += node_size[n]
        for e in node_edges[node_off[n]: node_off[n + 1]]:
            w = hg.edge_w[e]
            for m in edge_pin_cache[e]:
                if parts[m] < 0:
                    affinity[m, best] += w
    return parts


def partition_kway(hg: HostHypergraph, k: int, eps: float = 0.03,
                   n_cands: int = 4, theta: int = 16,
                   coarse_target: int | None = None,
                   use_kernels: bool = False, check_delta: bool = True,
                   collect_log: bool = False,
                   max_levels: int = 64,
                   plan=None, race: bool = True,
                   race_seed: int = 0,
                   dist_coarsen: bool = True,
                   compensated_psum: bool = False,
                   shard_graph: bool = False,
                   collect_stats: bool = False) -> PartitionResult:
    """k-way balanced partitioning; cut-net results from minimizing
    connectivity, exactly as the paper frames it.

    plan/race/race_seed/dist_coarsen/compensated_psum/shard_graph mirror
    `partitioner.partition`: with a `Plan`, each coarsening level runs
    mesh-sharded via `dist.partition.coarsen_level`/`contract_level` and
    each refinement level as mesh-raced replicas with sharded pipelines via
    `dist.partition.refine_level`; `shard_graph` memory-shards the
    pins-sized storage over the plan's "model" axis (`dist.graph`).
    `collect_stats` populates the quality side of
    `PartitionResult.level_stats` exactly as in `partitioner.partition`;
    phase wall-times are recorded as a "partition_kway" span tree and
    `timings` is a thin view over it."""
    omega = max(int((1 + eps) * hg.n_nodes / k), math.ceil(hg.n_nodes / k))
    with otrace.span("partition_kway", nodes=hg.n_nodes, edges=hg.n_edges,
                     k=k, omega=omega) as sp_total:
        with otrace.span("setup"):
            caps = Caps.for_host(hg)
            # exact int64 level-0 audit (see partitioner.partition): with
            # this passed the per-level int32 device counts cannot wrap
            check_expansion_caps(caps, host_pair_count(hg))
            if shard_graph:
                if plan is None or not dist_coarsen:
                    raise ValueError("shard_graph=True requires a Plan and "
                                     "dist_coarsen=True")
                from repro.dist.graph import sharded_from_host
                d = sharded_from_host(hg, caps, plan)
            else:
                d = device_from_host(hg, caps)
        cparams = CoarsenParams(omega=omega, delta=BIG_DELTA,
                                n_cands=n_cands, use_kernels=use_kernels)
        if coarse_target is None:
            coarse_target = min(4096, max(4 * k, 64))

        log: list = []
        _coarsen, _contract = make_coarsen_fns(cparams, plan, dist_coarsen,
                                               compensated=compensated_psum)
        # shared audited loop (one batched scalar sync + overflow audit per
        # level); blocks the dispatch tail so the phase span doesn't leak
        # into the host-side initial-partitioning step below
        with otrace.span("coarsen") as sp_coarsen:
            d, caps, levels, gammas, coarsen_hits, coarsen_meta = \
                run_coarsen_loop(d, caps, coarse_target, max_levels,
                                 _coarsen, _contract,
                                 log if collect_log else None)
        check_expansion_caps(caps, device_pair_count(d.edge_off))

        # ---- initial k-way on the coarsest graph (host, tiny) ------------
        with otrace.span("initial_kway"):
            if shard_graph:
                from repro.dist.graph import host_from_sharded
                coarse_host = host_from_sharded(d)
            else:
                coarse_host = host_from_device(d)
            coarse_sizes = np.asarray(d.node_size)[: coarse_host.n_nodes]
            init = greedy_initial_kway(coarse_host, coarse_sizes, k, omega)
            kcap = _next_pow2(k)
            parts = jnp.zeros((caps.n,), jnp.int32)
            parts = parts.at[: coarse_host.n_nodes].set(
                jnp.asarray(init, jnp.int32))

        rparams = RefineParams(omega=omega,
                               delta=BIG_DELTA if not check_delta
                               else BIG_DELTA,
                               theta=theta, use_kernels=use_kernels)

        rlog: list | None = [] if collect_log else None
        _refine = make_refine_fn(k, kcap, rparams, rlog, plan, race,
                                 race_seed)

        # shared uncoarsening-refinement loop (one batched telemetry
        # readback; kway's collect_log never logged refine entries -> None)
        parts, sp_refine, refine_meta, refine_hits, pins_hits = \
            run_refine_loop(d, parts, caps, levels, gammas, _refine, kcap,
                            omega, BIG_DELTA, collect_stats, None)
        refine_meta[len(levels)]["structure"] = dict(
            nodes=coarse_host.n_nodes, edges=int(d.n_edges),
            pins=int(d.n_pins))

        with otrace.span("audit"):
            parts_np = np.asarray(parts)[: hg.n_nodes].astype(np.int64)
            aud = metrics.audit(hg, parts_np, omega=omega, delta=BIG_DELTA)
            aud["balance_eps"] = metrics.balance_epsilon(parts_np, k)
    return PartitionResult(
        parts=parts_np, n_parts=int(parts_np.max()) + 1,
        n_levels=len(gammas),
        connectivity=aud["connectivity"], cut_net=aud["cut_net"], audit=aud,
        timings=dict(total=sp_total.duration, coarsen=sp_coarsen.duration,
                     refine=sp_refine.duration),
        level_log=(log or []) + (rlog or []),
        kernel_path=dict(coarsen=coarsen_hits, refine=refine_hits,
                         pins=pins_hits),
        level_stats=ovcycle.assemble(coarsen_meta, refine_meta))


def repartition_kway(hg: HostHypergraph, prev_parts, k: int,
                     eps: float = 0.03, *, deltas=None,
                     drift_threshold: float = 0.25, cache=None,
                     n_cands: int = 4, theta: int = 16,
                     coarse_target: int | None = None,
                     use_kernels: bool = False,
                     collect_log: bool = False, max_levels: int = 64,
                     plan=None, race: bool = True, race_seed: int = 0,
                     dist_coarsen: bool = True,
                     compensated_psum: bool = False,
                     shard_graph: bool = False,
                     collect_stats: bool = False) -> PartitionResult:
    """k-way sibling of `partitioner.repartition`: apply ``deltas`` to
    ``hg`` in place, then re-refine from ``prev_parts`` with the k-way
    constraint frame (Omega recomputed from the post-delta node count,
    Delta = +inf), falling back to a cold `partition_kway` when drift
    exceeds the threshold or the warm result breaks balance. ``n_parts=k``
    is pinned so trailing empty partitions keep their ids."""
    from repro.core.hypergraph import (CapacityError, GraphDelta,
                                       apply_delta, check_fits_caps)
    from repro.core.partitioner import WarmCache, _extend_parts, refine_from

    if isinstance(deltas, GraphDelta):
        deltas = [deltas]
    for dl in (deltas or []):
        apply_delta(hg, dl)
        if cache is not None and cache.caps is not None:
            cache.d = None
            try:
                check_fits_caps(hg, cache.caps)
            except CapacityError:
                cache.invalidate()

    omega = max(int((1 + eps) * hg.n_nodes / k), math.ceil(hg.n_nodes / k))
    parts0 = _extend_parts(prev_parts, hg.n_nodes, k)

    def _cold(mode: str) -> PartitionResult:
        res = partition_kway(
            hg, k, eps, n_cands=n_cands, theta=theta,
            coarse_target=coarse_target, use_kernels=use_kernels,
            collect_log=collect_log, max_levels=max_levels, plan=plan,
            race=race, race_seed=race_seed, dist_coarsen=dist_coarsen,
            compensated_psum=compensated_psum, shard_graph=shard_graph,
            collect_stats=collect_stats)
        res.mode = mode
        hg.reset_drift()
        if cache is not None:
            cache.invalidate()
        return res

    if hg.drift > drift_threshold:
        return _cold("fallback-drift")

    wc = cache if cache is not None else WarmCache()
    if wc.caps is None:
        wc.d = None
        wc.caps = Caps.for_host(hg)
        check_expansion_caps(wc.caps, host_pair_count(hg))
    if wc.d is None:
        if shard_graph and plan is not None:
            from repro.dist.graph import sharded_from_host
            wc.d = sharded_from_host(hg, wc.caps, plan)
        else:
            wc.d = device_from_host(hg, wc.caps)
    res = refine_from(
        hg, parts0, omega, BIG_DELTA, n_parts=k, theta=theta,
        use_kernels=use_kernels, collect_log=collect_log, plan=plan,
        race=race, race_seed=race_seed, shard_graph=shard_graph,
        collect_stats=collect_stats, device_graph=wc.d, caps=wc.caps,
        mode="warm")
    res.audit["balance_eps"] = metrics.balance_epsilon(res.parts, k)
    if not res.audit["size_ok"]:
        return _cold("fallback-audit")
    return res
