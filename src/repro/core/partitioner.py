"""Multi-level partitioner driver (paper Sec. III, Fig. 1).

Coarsen level-by-level (clusters capped at 2 nodes per level) until the
minimum valid partition count ceil(|N|/Omega) is reached or no further valid
clusters can be built; the coarsest clusters ARE the initial partitions
(score/connectivity duality, Eq. 2 vs Eq. 1); then uncoarsen with Theta
refinement repetitions per level.

Host Python drives the level loop (the level count is data-dependent, as on
GPU where the host launches kernels per level); every level step is one
fused jit at a single static capacity signature, so the whole run compiles
exactly once per input bucket.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.core.contract import contract
from repro.core.coarsen import CoarsenParams, coarsen_step
from repro.core.hypergraph import (Caps, GraphDelta, HostHypergraph,
                                   CapacityError, apply_delta,
                                   check_expansion_caps, check_fits_caps,
                                   device_from_host, device_pair_count,
                                   host_pair_count)
from repro.core.refine import LEVEL_COUNTERS, RefineParams, refine_level
from repro.obs import trace as otrace
from repro.obs import vcycle as ovcycle


@dataclasses.dataclass
class PartitionResult:
    parts: np.ndarray          # [N] partition id per node
    n_parts: int
    n_levels: int
    connectivity: float
    cut_net: float
    audit: dict
    # thin view over the span tree (same floats): kept for API compat, the
    # span tree (repro.obs.trace) is the source of truth for phase timing
    timings: dict
    level_log: list
    # per-level Pallas dispatch coverage (empty when use_kernels=False):
    #   "coarsen": [0/1 per coarsening level, finest first]
    #   "refine":  [gains-kernel reps (0..theta, of those that ran) per
    #               refined level, finest first; the last entry is the
    #               coarsest level]
    #   "pins":    [pins-count-kernel reps per refined level, same layout]
    kernel_path: dict = dataclasses.field(default_factory=dict)
    # per-level telemetry (repro.obs.vcycle.LevelStats, finest first;
    # quality fields populated under collect_stats=True)
    level_stats: list = dataclasses.field(default_factory=list)
    # how this result was produced: "cold" (full V-cycle), "warm"
    # (refine-only from a previous partition), or "fallback-drift" /
    # "fallback-audit" (repartition() fell back to a full V-cycle)
    mode: str = "cold"


def _next_pow2(x: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, x))))


def make_coarsen_fns(cparams: CoarsenParams, plan, dist_coarsen: bool = True,
                     compensated: bool = False):
    """Per-level coarsening dispatchers shared by `partition` and
    `kway.partition_kway`: returns `(coarsen(d, caps) -> (match, n_pairs,
    (n_pairs_live, n_nbr_entries, kernel_path_taken)),
    contract(d, match, caps) -> (d2, gamma))`. With a `Plan` (and
    `dist_coarsen`), both run on the mesh via `dist.partition.coarsen_level`
    / `contract_level` — bit-exact with the single-device pair at matching
    `use_kernels` (the mesh runs the Pallas kernels stripe-locally, and
    the dispatch branch taken per level is mesh-independent — see
    `repro.kernels`). ``compensated`` opts the eta / matching-sum0 float
    reductions into the Neumaier-compensated psum (O(dense) traffic, ~1
    ulp, not bit-identical).

    Both dispatchers return the same shapes in either mode; `_coarsen`'s
    trailing diagnostics feed the drivers' host-side capacity-overflow
    audit (`check_expansion_caps`) and the per-level kernel-coverage
    accounting (`PartitionResult.kernel_path`)."""
    if plan is None or not dist_coarsen:
        def _coarsen(d_, caps_):
            match, n_pairs, props = coarsen_step(d_, caps_, cparams)
            return match, n_pairs, (props.n_pairs_live, props.n_nbr_entries,
                                    props.kernel_path_taken)

        def _contract(d_, match_, caps_):
            return contract(d_, match_, caps_)
    else:
        import repro.dist.partition as dist_partition

        def _coarsen(d_, caps_):
            return dist_partition.coarsen_level(d_, caps_, cparams, plan,
                                                compensated=compensated)

        def _contract(d_, match_, caps_):
            return dist_partition.contract_level(d_, match_, caps_, plan)
    return _coarsen, _contract


def make_refine_fn(k, kcap: int, rparams: RefineParams, rlog,
                   plan, race: bool, race_seed: int):
    """Per-level refinement dispatcher shared by `partition` and
    `kway.partition_kway`: plain `refine_level` without a plan, the
    mesh-raced/sharded `dist.partition.refine_level` with one (seed offset
    by level so replica tie-break permutations decorrelate across levels).
    Returns `fn(d, parts, caps, level) -> (parts, counters)` — the level's
    int32[7] device vector in `refine.LEVEL_COUNTERS` order (Pallas-branch
    repetitions of the gains / pins dispatch, moves proposed and applied,
    repetitions skipped, repetitions that applied no move, theta). Theta
    repetitions are dispatched; without a plan each half-level stops at its
    first repetition that applies no move (`refine.refine_level`), on a
    mesh every repetition runs (racing makes each one differ)."""
    if plan is None:
        def _refine(d_, parts_, caps_, lvl_):
            return refine_level(d_, parts_, k, caps_, kcap, rparams, rlog)
    else:
        import repro.dist.partition as dist_partition

        def _refine(d_, parts_, caps_, lvl_):
            return dist_partition.refine_level(
                d_, parts_, k, caps_, kcap, rparams, plan, race=race,
                seed=race_seed + lvl_, log=rlog)
    return _refine


def run_coarsen_loop(d, caps: Caps, target: int, max_levels: int,
                     _coarsen, _contract, log: list | None,
                     shrink: bool = False):
    """Host-driven audited coarsening loop shared by `partition` and
    `kway.partition_kway`: per level, one batched device sync for the four
    scalars, a `check_expansion_caps` overflow audit BEFORE trusting the
    matches (the device pipelines drop out-of-capacity lanes silently), stop
    on `n_pairs == 0` or `target`. Returns
    ``(d, caps, levels, gammas, coarsen_hits, coarsen_meta)`` with
    ``levels`` a list of ``(d, caps)`` per retained level (caps varies only
    under ``shrink``, the pow2 re-bucketing mode) and ``coarsen_meta`` one
    structural-stats dict per retained level (nodes/edges/pins, live pair
    and neighborhood counts with their capacity occupancy, kernel path) —
    assembled from the same batched per-level sync, so telemetry adds no
    round-trips. Blocks on the dispatch tail before returning so the
    caller's phase timer doesn't leak into the next phase."""
    from repro.core.hypergraph import shrink_device

    levels, gammas, coarsen_hits, coarsen_meta = [], [], [], []
    while (int(otrace.device_sync(d.n_nodes, "coarsen_nodes")) > target
           and len(gammas) < max_levels):
        with otrace.span("coarsen_level", level=len(gammas)):
            match, n_pairs, ovf = _coarsen(d, caps)
            (pairs_live, nbr_entries, kern_hit, n_pairs_h, nodes_h, edges_h,
             pins_h) = (int(v) for v in otrace.device_sync(
                 [*ovf, n_pairs, d.n_nodes, d.n_edges, d.n_pins],
                 "level_scalars"))
            check_expansion_caps(caps, pairs_live, nbr_entries)
            if n_pairs_h == 0:
                break
            coarsen_hits.append(kern_hit)
            coarsen_meta.append(dict(
                nodes=nodes_h, edges=edges_h, pins=pins_h,
                pairs_live=pairs_live, nbr_entries=nbr_entries,
                pair_occupancy=pairs_live / caps.pairs,
                nbr_occupancy=nbr_entries / caps.nbrs,
                kernel_coarsen=kern_hit))
            d2, gamma = _contract(d, match, caps)
            if log is not None:
                log.append(dict(kind="coarsen", level=len(gammas),
                                nodes=nodes_h, pairs=n_pairs_h,
                                caps_n=caps.n))
            levels.append((d, caps))
            gammas.append(gamma)
            d = d2
            if shrink:
                d, caps = shrink_device(d, caps)
    otrace.device_sync((d, gammas), "coarsen_tail", jax.block_until_ready)
    return d, caps, levels, gammas, coarsen_hits, coarsen_meta


def run_refine_loop(d, parts, caps: Caps, levels, gammas, _refine,
                    kcap: int, omega: int, delta: int,
                    collect_stats: bool, log: list | None):
    """Host-driven uncoarsening refinement loop shared by `partition`,
    `kway.partition_kway`, and the warm-start entry `refine_from`: refine
    the coarsest (or only) level, then project through each ``gammas[lvl]``
    and refine every retained level, finest last. Runs under a "refine"
    span with one "refine_level" span per level; kernel-dispatch hits and
    quality scalars stay device values until ONE batched readback at the
    end, so telemetry adds no per-level syncs. Blocks the dispatch tail
    before the span closes.

    Returns ``(parts, refine_span, refine_meta, refine_hits, pins_hits)``
    — ``refine_meta`` one dict per refined level (its
    `refine.LEVEL_COUNTERS` and ``quality``, for `obs.vcycle.assemble`), the
    hits lists the per-level Pallas-branch repetition counts (gains / pins
    dispatch) for ``PartitionResult.kernel_path``. Each ``refine_level``
    span is annotated with its ``moves_proposed``, ``moves_applied``,
    ``skipped_reps``, ``noop_reps`` and ``reps`` once the readback has
    them. With ``levels=[]`` (warm start) this is a single-level refine of
    ``d`` — no projection, no coarsening."""
    quality_dev: dict = {}
    counters_dev: dict = {}
    level_spans: dict = {}
    with otrace.span("refine") as sp_refine:
        with otrace.span("refine_level", level=len(levels)) as sp:
            parts, counters_dev[len(levels)] = _refine(d, parts, caps,
                                                       len(levels))
        level_spans[len(levels)] = sp
        if collect_stats:
            quality_dev[len(levels)] = ovcycle.quality_scalars(
                d, parts, caps, kcap, omega, delta)
        for lvl in range(len(levels) - 1, -1, -1):
            g = gammas[lvl]
            d_lvl, caps_lvl = levels[lvl]
            coarse_cap = parts.shape[0]
            with otrace.span("refine_level", level=lvl) as sp:
                parts = jnp.where(
                    jnp.arange(caps_lvl.n) < d_lvl.n_nodes,
                    parts[jnp.clip(g[: caps_lvl.n], 0, coarse_cap - 1)], 0)
                parts, counters_dev[lvl] = _refine(d_lvl, parts, caps_lvl,
                                                   lvl)
            level_spans[lvl] = sp
            if collect_stats:
                quality_dev[lvl] = ovcycle.quality_scalars(
                    d_lvl, parts, caps_lvl, kcap, omega, delta)
            if log is not None:
                log.append(dict(kind="refine", level=lvl))
        # block before the span closes: the refine tail would otherwise
        # drain inside the caller's parts readback, after the timer stopped
        otrace.device_sync(parts, "refine_tail", jax.block_until_ready)
    # ONE batched readback for the level counters + quality scalars
    counters_h, quality_h = otrace.device_sync(
        ([counters_dev[i] for i in range(len(levels) + 1)], quality_dev),
        "refine_readback")
    refine_meta = {}
    for lvl, c in enumerate(counters_h):
        cnt = dict(zip(LEVEL_COUNTERS, (int(v) for v in c)))
        level_spans[lvl].annotate(**{k: cnt[k] for k in LEVEL_COUNTERS[2:]})
        refine_meta[lvl] = dict(cnt, quality=quality_h.get(lvl))
    refine_hits = [m["kernel_hits"] for m in refine_meta.values()]
    pins_hits = [m["pins_hits"] for m in refine_meta.values()]
    return parts, sp_refine, refine_meta, refine_hits, pins_hits


def vcycle_device(d, omega, delta, caps: Caps, kcap: int,
                  n_cands: int = 4, theta: int = 16, max_levels: int = 16,
                  chain_rounds: int = 16):
    """Pure-device masked V-cycle: the whole multi-level solve as one traced
    function with NO host round-trips — the vmap-friendly batched entry the
    partition service (`serve.partition_service`) maps over padded request
    batches.

    The host driver's data-dependent level loop becomes a fixed-length
    `lax.scan` over ``max_levels`` with per-level ``active`` masks: a level
    whose coarsening stopped (``n_nodes <= ceil(n/omega)`` or zero matched
    pairs) keeps its graph and partition unchanged, so the scan replays the
    host loop's break semantics exactly (re-coarsening an unchanged graph is
    deterministic, hence stays stopped). ``omega``/``delta`` are *traced*
    int32 scalars — requests with different constraints share one compile.
    ``caps``/``kcap`` are static: one jit signature per capacity bucket.
    ``use_kernels`` is off (Pallas dispatch under vmap is out of scope — the
    service batches small graphs where the segment path wins anyway).

    Returns a dict of device values: ``parts [caps.n]`` (uncompacted,
    0 beyond ``n_nodes``), ``n_parts`` (coarsest-level count, before
    host-side id compaction), ``n_levels``, and the overflow diagnostics
    ``pairs_live_max`` / ``nbr_entries_max`` — per-level maxima the caller
    must audit host-side via `check_expansion_caps` (pair totals are
    monotone under coarsening, so a passed level-0 audit already bounds
    them; this is the defense-in-depth recheck).

    Bit-exactness: at matching ``caps``/``kcap``/params this reproduces
    `partition(...)` (bucket=False, use_kernels=False,
    ``kcap_hint=kcap``) exactly — verified in
    ``tests/test_partition_service.py``."""
    from repro.core.coarsen import coarsen_step_impl
    from repro.core.contract import contract_impl
    from repro.core.refine import refine_step_impl

    omega = jnp.asarray(omega, jnp.int32)
    delta = jnp.asarray(delta, jnp.int32)
    cparams = CoarsenParams(omega=omega, delta=delta, n_cands=n_cands,
                            use_kernels=False)
    rparams = RefineParams(omega=omega, delta=delta, theta=theta,
                           use_kernels=False, chain_rounds=chain_rounds)
    target = jnp.maximum(jnp.int32(1),
                         (d.n_nodes + omega - jnp.int32(1)) // omega)

    def coarsen_body(carry, _):
        d, pmax, nmax = carry
        entering = d.n_nodes > target
        match, n_pairs, props = coarsen_step_impl(d, caps, cparams)
        active = entering & (n_pairs > 0)
        pmax = jnp.maximum(pmax, jnp.where(entering, props.n_pairs_live, 0))
        nmax = jnp.maximum(nmax, jnp.where(entering, props.n_nbr_entries, 0))
        d2, gamma = contract_impl(d, match, caps)
        # inactive level: keep the graph — contract() of a stopped level
        # would still re-canonicalize pin order, which must not happen
        d_next = jax.tree.map(lambda a, b: jnp.where(active, a, b), d2, d)
        return (d_next, pmax, nmax), (d, gamma, active)

    (d, pmax, nmax), (levels_d, gammas, actives) = jax.lax.scan(
        coarsen_body, (d, jnp.int32(0), jnp.int32(0)), None,
        length=max_levels)
    # the coarsest graph is refined but never re-enters coarsening: audit
    # its pair expansion too (refinement expands the same pairs)
    pmax = jnp.maximum(pmax, device_pair_count(d.edge_off))

    k = d.n_nodes
    parts = jnp.where(jnp.arange(caps.n) < k,
                      jnp.arange(caps.n, dtype=jnp.int32), 0)

    enforce = jnp.arange(theta) >= (theta // 2)

    def refine_one_level(d_lvl, parts):
        def rep(parts, enf):
            parts2, *_ = refine_step_impl(d_lvl, parts, k, caps, kcap,
                                          rparams, enf)
            return parts2, None
        parts, _ = jax.lax.scan(rep, parts, enforce)
        return parts

    parts = refine_one_level(d, parts)  # coarsest level
    coarse_cap = parts.shape[0]

    def uncoarsen_body(parts, level):
        d_lvl, gamma, active = level
        proj = jnp.where(jnp.arange(caps.n) < d_lvl.n_nodes,
                         parts[jnp.clip(gamma, 0, coarse_cap - 1)], 0)
        parts_in = jnp.where(active, proj, parts)
        refined = refine_one_level(d_lvl, parts_in)
        return jnp.where(active, refined, parts), None

    parts, _ = jax.lax.scan(uncoarsen_body, parts,
                            (levels_d, gammas, actives), reverse=True)
    return dict(parts=parts, n_parts=k,
                n_levels=jnp.sum(actives.astype(jnp.int32)),
                pairs_live_max=pmax, nbr_entries_max=nmax)


@functools.lru_cache(maxsize=None)
def _batch_solver(caps: Caps, kcap: int, n_cands: int, theta: int,
                  max_levels: int, chain_rounds: int):
    """One jitted vmapped solver per bucket signature (lru-cached so every
    batch a bucket ever solves shares the same compiled executable)."""
    return jax.jit(
        jax.vmap(lambda d_, o_, dl_: vcycle_device(
            d_, o_, dl_, caps, kcap, n_cands=n_cands, theta=theta,
            max_levels=max_levels, chain_rounds=chain_rounds)))


def partition_batch_device(batch, omega, delta, caps: Caps, kcap: int,
                           n_cands: int = 4, theta: int = 16,
                           max_levels: int = 16, chain_rounds: int = 16):
    """vmap of `vcycle_device` over a stacked batch of capacity-padded
    device hypergraphs (every leaf gains a leading batch axis; see
    `serve.partition_service.stack_device_batch`). ``omega``/``delta`` are
    ``[B]`` int32 vectors — per-request constraints inside one solve. One
    jit cache entry per ``(caps, kcap, n_cands, theta, max_levels,
    chain_rounds)`` bucket signature, shared across every batch the bucket
    ever solves."""
    return _batch_solver(caps, kcap, n_cands, theta, max_levels,
                         chain_rounds)(batch, omega, delta)


def partition(hg: HostHypergraph, omega: int, delta: int,
              n_cands: int = 4, theta: int = 16, use_kernels: bool = False,
              refine_params: RefineParams | None = None,
              max_levels: int = 64, collect_log: bool = False,
              kcap_hint: int | None = None,
              matching: str = "exact",
              chain_rounds: int = 16,
              bucket: bool = False,
              plan=None, race: bool = True,
              race_seed: int = 0,
              dist_coarsen: bool = True,
              compensated_psum: bool = False,
              shard_graph: bool = False,
              pair_cap: int | None = None,
              nbr_cap: int | None = None,
              collect_stats: bool = False) -> PartitionResult:
    """Full multi-level constrained partitioning (paper's SNN mode).

    bucket=True enables pow2 capacity re-bucketing between levels
    (`hypergraph.shrink_device`) — identical results, coarse levels run on
    geometrically shrinking arrays.

    plan (a `repro.dist.Plan`) routes the whole V-cycle onto the mesh:
    every coarsening level runs through `dist.partition.coarsen_level` /
    `contract_level` (pins/pairs pipelines sharded across the model axis,
    bit-exact with the single-device path at matching `use_kernels` — the
    Pallas hot loops run stripe-locally on the mesh, see `repro.kernels`;
    `dist_coarsen=False` keeps coarsening single-device) and every
    refinement level through
    `dist.partition.refine_level`: repetitions race as replicas across the
    mesh's data axis (`race=False` for the deterministic parity mode) and
    the pins-sized pipelines shard across its model axis. `race_seed`
    decorrelates the replica tie-break permutations. `compensated_psum`
    opts the coarsening eta / matching-sum0 float reductions into the
    Neumaier-compensated psum (O(dense) traffic instead of the stripe-order
    lane gather; within ~1 ulp but not bit-identical to one device).

    shard_graph=True additionally memory-shards the graph *storage*: the
    pins-sized arrays of every level live as per-shard stripes over the
    plan's "model" axis (`dist.graph.ShardedHypergraph`; racing replicas
    share the one striped copy) — bit-identical results, O(pins / shards)
    storage per device. Requires `plan` and `dist_coarsen`; incompatible
    with `bucket` (re-bucketing would re-slice the fixed stripe layout).

    pair_cap / nbr_cap override `Caps.for_host`'s exact pair-expansion /
    neighborhood capacities (e.g. to bound memory). Undersizing them does
    not silently truncate: every level's live counts are audited host-side
    and overflow raises `CapacityError`.

    collect_stats=True additionally populates the quality side of
    `PartitionResult.level_stats` (per-level connectivity/cut of the
    projected partition, block-size and distinct-incident-hyperedge slack
    vs Omega/Delta — `repro.obs.vcycle`): a few extra device reductions per
    level, fetched in one batched readback at the end. Telemetry only reads
    the solve's values, so results are bit-identical either way (tested).
    Phase wall-times are recorded as an `repro.obs.trace` span tree
    ("partition" > setup/coarsen/refine/audit); the ``timings`` dict on the
    result is a thin view over the same spans.
    """
    with otrace.span("partition", nodes=hg.n_nodes, edges=hg.n_edges,
                     pins=hg.n_pins, omega=omega, delta=delta) as sp_total:
        with otrace.span("setup") as sp_setup:
            caps = Caps.for_host(hg, pair_cap=pair_cap, nbr_cap=nbr_cap)
            # exact int64 level-0 audit before any device work: with this
            # passed, pair monotonicity under coarsening bounds every
            # level's count by caps.pairs < 2**31, making the per-level
            # int32 device counts exact
            check_expansion_caps(caps, host_pair_count(hg))
            if shard_graph:
                if plan is None:
                    raise ValueError(
                        "shard_graph=True requires a Plan (mesh) — "
                        "graph stripes live on its 'model' axis")
                if not dist_coarsen:
                    raise ValueError(
                        "shard_graph=True requires dist_coarsen=True: "
                        "the single-device coarsen path cannot read "
                        "memory-sharded storage")
                if bucket:
                    raise ValueError(
                        "bucket=True is incompatible with shard_graph=True: "
                        "capacity re-bucketing would re-slice the fixed "
                        "stripe layout")
                from repro.dist.graph import sharded_from_host
                d = sharded_from_host(hg, caps, plan)
            else:
                d = device_from_host(hg, caps)
            sp_setup.sync(d)  # the upload drains here, not in coarsening
        cparams = CoarsenParams(omega=omega, delta=delta, n_cands=n_cands,
                                use_kernels=use_kernels, matching=matching)

        target = max(1, math.ceil(hg.n_nodes / omega))
        log: list = []
        _coarsen, _contract = make_coarsen_fns(cparams, plan, dist_coarsen,
                                               compensated=compensated_psum)
        # run_coarsen_loop: per level one batched scalar sync + overflow
        # audit BEFORE trusting the matches, then blocks the dispatch tail
        # so the phase span doesn't leak into refinement
        with otrace.span("coarsen") as sp_coarsen:
            d, caps, levels, gammas, coarsen_hits, coarsen_meta = \
                run_coarsen_loop(d, caps, target, max_levels, _coarsen,
                                 _contract, log if collect_log else None,
                                 shrink=bucket)
        # the coarsest graph is refined below but never re-entered
        # coarsening, so audit its pair expansion (refinement's in-sequence
        # gains expand the same pairs) — every earlier level was audited in
        # the loop
        check_expansion_caps(caps, otrace.device_sync(
            device_pair_count(d.edge_off), "pair_count"))

        # initial partitioning == coarsest clusters (Sec. III)
        k = int(otrace.device_sync(d.n_nodes, "coarsest_nodes"))
        if kcap_hint is None:
            kcap = _next_pow2(k)
        else:
            if kcap_hint < k:
                raise ValueError(
                    f"kcap_hint={kcap_hint} is below the coarsest partition "
                    f"count k={k}: partition ids would be silently clipped. "
                    f"Pass kcap_hint >= k (or None for the default pow2).")
            kcap = kcap_hint
        parts = jnp.where(jnp.arange(caps.n) < k,
                          jnp.arange(caps.n, dtype=jnp.int32), 0)

        rparams = refine_params or RefineParams(
            omega=omega, delta=delta, theta=theta, use_kernels=use_kernels,
            chain_rounds=chain_rounds)

        rlog: list | None = [] if collect_log else None
        _refine = make_refine_fn(k, kcap, rparams, rlog, plan, race,
                                 race_seed)

        structure = dict(
            nodes=k,
            edges=int(otrace.device_sync(d.n_edges, "coarsest_edges")),
            pins=int(otrace.device_sync(d.n_pins, "coarsest_pins")))

        # refine the coarsest level too, then every uncoarsened level
        # (shared with kway/refine_from; one batched readback at the end)
        parts, sp_refine, refine_meta, refine_hits, pins_hits = \
            run_refine_loop(d, parts, caps, levels, gammas, _refine, kcap,
                            omega, delta, collect_stats,
                            log if collect_log else None)
        refine_meta[len(levels)]["structure"] = structure

        with otrace.span("audit"):
            parts_np = otrace.device_sync(parts, "parts")[: hg.n_nodes] \
                .astype(np.int64)
            # compact partition ids (refinement may empty some partitions)
            uniq, parts_np = np.unique(parts_np, return_inverse=True)
            aud = metrics.audit(hg, parts_np, omega=omega, delta=delta)
    return PartitionResult(
        parts=parts_np, n_parts=len(uniq), n_levels=len(gammas),
        connectivity=aud["connectivity"], cut_net=aud["cut_net"], audit=aud,
        timings=dict(total=sp_total.duration, coarsen=sp_coarsen.duration,
                     refine=sp_refine.duration),
        level_log=(log or []) + (rlog or []),
        kernel_path=dict(coarsen=coarsen_hits, refine=refine_hits,
                         pins=pins_hits),
        level_stats=ovcycle.assemble(coarsen_meta, refine_meta))


# ---------------------------------------------------------------------------
# Streaming repartitioning: warm-started refine-only solves
# ---------------------------------------------------------------------------
def refine_from(hg: HostHypergraph, parts, omega: int, delta: int,
                *, n_parts: int | None = None, theta: int = 16,
                use_kernels: bool = False,
                refine_params: RefineParams | None = None,
                collect_log: bool = False,
                kcap_hint: int | None = None,
                chain_rounds: int = 16,
                plan=None, race: bool = True, race_seed: int = 0,
                shard_graph: bool = False,
                pair_cap: int | None = None, nbr_cap: int | None = None,
                collect_stats: bool = False,
                device_graph=None, caps: Caps | None = None,
                mode: str = "warm") -> PartitionResult:
    """Standalone refinement: the theta-rep refine loop of `partition()`
    applied to an *existing* partition vector, skipping coarsening
    entirely (``n_levels == 0``; the span tree contains no
    ``coarsen_level`` spans by construction).

    ``parts`` is a host vector of at least ``hg.n_nodes`` partition ids;
    ``n_parts`` overrides the inferred partition count (``max+1``) —
    required when trailing partitions happen to be empty but ids must stay
    stable (the k-way warm path). ``plan``/``race``/``shard_graph`` mirror
    `partition()`: with a mesh the refinement levels race replicas over
    "data" and shard the pins pipelines over "model", bit-identical at
    ``race=False``.

    ``device_graph``/``caps`` short-circuit the device upload: the caller
    (``repartition``'s warm cache) already holds graph storage at a known
    capacity signature — reusing it keeps the jit cache warm across
    resubmits. Both must be given together and are trusted to match ``hg``.
    """
    with otrace.span("partition", nodes=hg.n_nodes, edges=hg.n_edges,
                     pins=hg.n_pins, omega=omega, delta=delta,
                     mode=mode) as sp_total:
        with otrace.span("setup") as sp_setup:
            if (device_graph is None) != (caps is None):
                raise ValueError(
                    "device_graph and caps must be passed together")
            if caps is None:
                caps = Caps.for_host(hg, pair_cap=pair_cap, nbr_cap=nbr_cap)
                # exact int64 audit before any device work (refinement's
                # in-sequence gains expand the same pin pairs)
                check_expansion_caps(caps, host_pair_count(hg))
                if shard_graph:
                    if plan is None:
                        raise ValueError(
                            "shard_graph=True requires a Plan (mesh)")
                    from repro.dist.graph import sharded_from_host
                    d = sharded_from_host(hg, caps, plan)
                else:
                    d = device_from_host(hg, caps)
            else:
                d = device_graph

            parts_in = np.asarray(parts, np.int64).ravel()
            if parts_in.shape[0] < hg.n_nodes:
                raise ValueError(
                    f"parts has {parts_in.shape[0]} entries for "
                    f"{hg.n_nodes} nodes — apply deltas (which may add "
                    f"nodes) via repartition(), or extend the vector")
            parts_in = parts_in[: hg.n_nodes]
            if parts_in.size and parts_in.min() < 0:
                raise ValueError("parts must be non-negative")
            k = (int(parts_in.max(initial=0)) + 1 if n_parts is None
                 else int(n_parts))
            if parts_in.size and int(parts_in.max(initial=0)) >= k:
                raise ValueError(
                    f"n_parts={k} is below max partition id "
                    f"{int(parts_in.max())}")
            if kcap_hint is None:
                kcap = _next_pow2(k)
            else:
                if kcap_hint < k:
                    raise ValueError(
                        f"kcap_hint={kcap_hint} is below the partition "
                        f"count k={k}")
                kcap = kcap_hint
            parts_dev = jnp.zeros((caps.n,), jnp.int32).at[: hg.n_nodes].set(
                jnp.asarray(parts_in, jnp.int32))
            sp_setup.sync((d, parts_dev))

        rparams = refine_params or RefineParams(
            omega=omega, delta=delta, theta=theta, use_kernels=use_kernels,
            chain_rounds=chain_rounds)
        rlog: list | None = [] if collect_log else None
        _refine = make_refine_fn(k, kcap, rparams, rlog, plan, race,
                                 race_seed)

        parts_dev, sp_refine, refine_meta, refine_hits, pins_hits = \
            run_refine_loop(d, parts_dev, caps, [], [], _refine, kcap,
                            omega, delta, collect_stats,
                            rlog if collect_log else None)
        refine_meta[0]["structure"] = dict(
            nodes=hg.n_nodes, edges=hg.n_edges, pins=hg.n_pins)

        with otrace.span("audit"):
            parts_np = otrace.device_sync(parts_dev, "parts")[: hg.n_nodes] \
                .astype(np.int64)
            if n_parts is None:
                uniq, parts_np = np.unique(parts_np, return_inverse=True)
                n_out = len(uniq)
            else:
                # pinned id space (k-way warm path): empty partitions keep
                # their ids, no compaction
                n_out = k
            aud = metrics.audit(hg, parts_np, omega=omega, delta=delta)
    return PartitionResult(
        parts=parts_np, n_parts=n_out, n_levels=0,
        connectivity=aud["connectivity"], cut_net=aud["cut_net"], audit=aud,
        timings=dict(total=sp_total.duration, coarsen=0.0,
                     refine=sp_refine.duration),
        level_log=rlog or [],
        kernel_path=dict(coarsen=[], refine=refine_hits, pins=pins_hits),
        level_stats=ovcycle.assemble([], refine_meta),
        mode=mode)


@dataclasses.dataclass
class WarmCache:
    """Caller-owned device-storage cache for `repartition`: the capacity
    signature and graph storage of the last solve. A valid cache lets a
    resubmit skip both `Caps.for_host` and the full host->device upload
    (sharded storage updates by stripe-local scatters), and — because the
    caps are unchanged — reuse every compiled executable. `repartition`
    mutates it in place; pass a fresh instance (or None) to start cold."""

    caps: Caps | None = None
    d: object | None = None   # DeviceHypergraph | ShardedHypergraph

    def invalidate(self) -> None:
        self.caps = None
        self.d = None


def _extend_parts(prev_parts, n_nodes: int, k: int) -> np.ndarray:
    """Deterministic placement for nodes added since the previous solve:
    each new node joins the currently least-loaded partition (ties ->
    lowest id), updating loads as it goes. Node deletions are tombstones
    (ids stable), so existing entries never shift."""
    prev = np.asarray(prev_parts, np.int64).ravel()
    if prev.shape[0] >= n_nodes:
        return prev[:n_nodes]
    sizes = np.bincount(prev, minlength=max(k, 1))
    out = np.concatenate([prev, np.zeros(n_nodes - prev.shape[0], np.int64)])
    for n in range(prev.shape[0], n_nodes):
        p = int(np.argmin(sizes))
        out[n] = p
        sizes[p] += 1
    return out


def repartition(hg: HostHypergraph, prev_parts, omega: int, delta: int,
                *, deltas=None, drift_threshold: float = 0.25,
                cache: WarmCache | None = None,
                n_parts: int | None = None,
                theta: int = 16, n_cands: int = 4,
                use_kernels: bool = False,
                refine_params: RefineParams | None = None,
                collect_log: bool = False,
                kcap_hint: int | None = None,
                chain_rounds: int = 16, max_levels: int = 64,
                matching: str = "exact",
                plan=None, race: bool = True, race_seed: int = 0,
                dist_coarsen: bool = True, compensated_psum: bool = False,
                shard_graph: bool = False,
                pair_cap: int | None = None, nbr_cap: int | None = None,
                collect_stats: bool = False) -> PartitionResult:
    """Streaming repartitioning: apply ``deltas`` (a `GraphDelta` or a
    sequence of them) to ``hg`` **in place**, then re-solve warm from
    ``prev_parts`` — refinement only, no coarsening — falling back to a
    full cold V-cycle when the accumulated ``hg.drift`` exceeds
    ``drift_threshold`` or the warm solution fails the Omega/Delta +
    distinct-incident-hyperedge audit. The result's ``mode`` records which
    path produced it ("warm" / "fallback-drift" / "fallback-audit"; a
    zero-delta call with no cache is bit-identical to `refine_from`).

    ``cache`` (a `WarmCache`) carries device storage across calls: with a
    valid cache and sharded storage (``shard_graph`` + ``plan``) the deltas
    apply on device by stripe-local scatters
    (`dist.graph.apply_delta_sharded`); a `CapacityError` from the PR 5
    audit machinery — the post-delta graph outgrew the cached capacity
    signature — invalidates the cache and the solve proceeds warm at fresh
    caps (one re-upload + recompile, not a cold solve). Cold fallbacks
    reset the drift accumulator and invalidate the cache; warm solves keep
    accumulating drift, so repeated small deltas eventually trigger one
    consolidating cold solve."""
    from repro.core.hypergraph import DeviceHypergraph  # noqa: F401

    if isinstance(deltas, GraphDelta):
        deltas = [deltas]
    deltas = list(deltas or [])
    use_sharded = shard_graph and plan is not None

    for dl in deltas:
        if (use_sharded and cache is not None and cache.caps is not None
                and cache.d is not None):
            from repro.dist.graph import (ShardedHypergraph,
                                          apply_delta_sharded)
            if isinstance(cache.d, ShardedHypergraph):
                try:
                    cache.d = apply_delta_sharded(cache.d, hg, dl,
                                                  cache.caps, plan)
                except CapacityError:
                    # resize trigger: host mirror is updated; rebuild
                    # device storage at fresh caps, stay warm
                    cache.invalidate()
                continue
        apply_delta(hg, dl)
        if cache is not None and cache.caps is not None:
            cache.d = None  # replicated storage refreshes wholesale below
            try:
                check_fits_caps(hg, cache.caps)
            except CapacityError:
                cache.invalidate()

    k_prev = (int(np.asarray(prev_parts).max(initial=0)) + 1
              if n_parts is None else int(n_parts))
    parts0 = _extend_parts(prev_parts, hg.n_nodes, k_prev)

    cold_kwargs = dict(
        n_cands=n_cands, theta=theta, use_kernels=use_kernels,
        refine_params=refine_params, max_levels=max_levels,
        collect_log=collect_log, kcap_hint=kcap_hint, matching=matching,
        chain_rounds=chain_rounds, plan=plan, race=race,
        race_seed=race_seed, dist_coarsen=dist_coarsen,
        compensated_psum=compensated_psum, shard_graph=shard_graph,
        pair_cap=pair_cap, nbr_cap=nbr_cap, collect_stats=collect_stats)

    def _cold(mode: str) -> PartitionResult:
        res = partition(hg, omega, delta, **cold_kwargs)
        res.mode = mode
        hg.reset_drift()
        if cache is not None:
            cache.invalidate()
        return res

    if hg.drift > drift_threshold:
        return _cold("fallback-drift")

    # ---- warm path: reuse / rebuild device storage, refine only ----------
    wc = cache if cache is not None else WarmCache()
    if wc.caps is None:
        wc.d = None
        wc.caps = Caps.for_host(hg, pair_cap=pair_cap, nbr_cap=nbr_cap)
        check_expansion_caps(wc.caps, host_pair_count(hg))
    if wc.d is None:
        if use_sharded:
            from repro.dist.graph import sharded_from_host
            wc.d = sharded_from_host(hg, wc.caps, plan)
        else:
            wc.d = device_from_host(hg, wc.caps)
    res = refine_from(
        hg, parts0, omega, delta, n_parts=n_parts, theta=theta,
        use_kernels=use_kernels, refine_params=refine_params,
        collect_log=collect_log, kcap_hint=kcap_hint,
        chain_rounds=chain_rounds, plan=plan, race=race,
        race_seed=race_seed, shard_graph=shard_graph,
        collect_stats=collect_stats, device_graph=wc.d, caps=wc.caps,
        mode="warm")
    if not (res.audit["size_ok"] and res.audit["inbound_ok"]):
        return _cold("fallback-audit")
    return res
