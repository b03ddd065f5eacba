"""Mesh-sharded V-cycle drivers: sharded coarsening + raced/sharded
refinement.

The paper's 380x refinement speedup (Sec. VI) comes from two levels of
parallelism that a single-device run serializes: the Theta independent
repetitions per level, and the massive pins/pairs-sized kernels inside each
repetition. This module maps both onto a `Plan` mesh with one `shard_map`:

* **"data" axis — replicated racing repetitions.** Every device runs a full
  repetition from the same partition vector but with a *distinct tie-break
  permutation* threaded through chain construction (`build_sequence`'s sort
  keys, successor-claim argmax, and cycle-cut anchor). Replica 0 keeps the
  identity permutation, so the single-device trajectory is always in the
  race. After the events check, a tiny all-gather of the per-replica applied
  gains + argmax (ties -> lowest replica) picks the winner, whose applied
  prefix is broadcast with a psum of the masked partition vector — no
  partition-sized gather. Mt-KaHyPar-style independent repetitions, raced
  instead of sequenced.

* **"model" axis — sharded pins-sized pipelines.** Each pins/pairs-sized
  stage of `core.refine` processes one contiguous lane stripe per device
  (`segops.ShardCtx.lanes`) and combines *dense* per-node / per-partition
  segment outputs with psum — the all-gather-free segment reduction.
  Segmented scans over the sorted events run stripe-local with cross-shard
  carries (`segops.sharded_segmented_scan`).

Paper Sec. VI kernel -> sharded counterpart:

  pins(p,e) matrix precompute (VI-B)   -> `refine.pins_matrix(ctx)`: lane
      stripes + psum of the dense [kcap, Ecap] count matrices
  warp-per-node gain loops (VI-B)      -> `refine.propose_moves(ctx)`:
      striped incidence traversal, psum'd saving / w_tot / conn_w
  grade claims via atomics (VI-C)      -> replicated `build_sequence` with
      per-replica `tie_rank` (node-sized; raced, not sharded)
  pair-expansion Eq. 14/15 (VI-C)      -> `refine.inseq_gains(ctx)`: pair
      lanes striped via `build_pairs(idx)`, psum'd (n,e) counts
  CUB sort + segmented scan (VI-D)     -> `refine.events_validity(ctx)`:
      striped event construction, distributed sample sort (`dist.sort` via
      `ShardCtx.sort_by` — stripes in/out, only splitter samples gathered),
      stripe-local scans with cross-shard carries, psum'd violation deltas

Coarsening (`coarsen_level` / `contract_level`, paper Sec. V-B..V-E) shards
the same way over "model" and is deterministic, so it never races — on a
mesh with a data axis the replica rows simply compute identical levels.
Paper kernel -> sharded counterpart:

  pair-expansion scoring Eq. 5 (V-B/C)  -> `coarsen.score_slots(ctx)`: lane
      stripes + stripe-local segmented binary search
  in-histogram inter counter (Fig. 3)   -> same stripes, psum'd dense
      integer counts
  top-Pi candidate selection (V-C)      -> `coarsen.propose(ctx)`: Pi-round
      `segment_argmax` on slot-lane stripes, cross-shard lexicographic
      (value, id) pmax, winner slot retired on its owning shard
  matching DP wavefront Eq. 7-12 (V-D)  -> `matching.match_pseudoforest
      (ctx)`: replicated state, child-lane stripes per iteration
  contraction dedup + packing (V-E)     -> `contract.contract_impl(ctx)`:
      striped key construction, distributed sample sorts, stripe-local rank
      scans with cross-shard carries, psum'd disjoint scatters

What travels how — the exactness contract. Float32 addition is not
associative, so a psum of float partial sums lands within an ulp of — but
not bit-identical to — the single-device accumulation (measured: tens of
mismatched slots per level at 8 shards), which is enough to flip an argmax
and diverge the whole V-cycle. Every sharded reduction therefore picks one
of three combines:

  * psum     — integer counts only (inter, matching cnt ticks, contraction
               counts and disjoint pin scatters): exact in any order.
  * pmax     — (value, id) lexicographic claims (candidate rounds, matching
               best-child): pure maxes, exact in any order.
  * gather   — float sums (eta histograms, matching sum0 pushes) gather
               their lane columns in stripe order, i.e. the global lane
               order, and reduce replicated: the scatter-add order is then
               bit-identical to the single-device sweep. (Opt-in
               `compensated` trades this for a Neumaier-compensated psum
               of dense partials — O(dense) traffic, ~1 ulp, not
               bit-identical.) Sorts no longer gather at all: every sort is
               the distributed sample sort of `dist.sort`, whose global-rank
               tie key makes it bit-identical to the gathered stable
               `lax.sort` by construction.

Contraction is bit-exact by construction — its whole pipeline is integer —
so the contracted hypergraph, not just the final parts vector, matches the
single-device level byte-for-byte; refinement then starts each level from
identical state.

**Sharded graph storage** (`dist.graph.ShardedHypergraph`): the pins-sized
storage arrays may additionally arrive as per-shard lane stripes over
"model" instead of replicated copies (`--shard-graph`; racing replicas
then share the one sharded graph across "data"). The exactness rules
extend unchanged, because striping is pure layout:

  * own-stripe reads (`ShardCtx.gread`) return exactly the replicated
    array's values at this shard's lane positions — every pins/pairs
    pipeline stage already indexed only its own lanes;
  * the one arbitrary-position access (`build_pairs` joining two pin
    slots per pair lane) transiently rebuilds the pins column with the
    bit-preserving `ShardCtx.gfull` (psum of disjoint int32 stripes, the
    `unstripe` combine — never a float psum);
  * contraction emits the coarse pins arrays as stripes (reduce-scatter
    of the integer packing scatter + stripe-kept incidence sort) — the
    same integers the replicated path scatters, in the same slots, so
    levels stay striped end-to-end and stay bit-exact.

Exactness: with racing off (or on the 1-replica data axis) every replica
uses the identity permutation, and with the combine discipline above every
sharded stage of both coarsening and refinement reproduces the
single-device arithmetic exactly, so the full V-cycle is bit-identical to
`core.partitioner.partition` — with replicated *or* memory-sharded graph
storage — enforced by the parity tests in tests/test_dist_partition.py
under 8 forced host devices on (2, 4) and (1, 8) meshes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.coarsen import CoarsenParams, coarsen_step_impl
from repro.core.contract import contract_impl
from repro.core.hypergraph import Caps
from repro.core.refine import (RefineParams, level_counters, log_rep,
                               refine_step_impl)
from repro.dist.graph import ShardedHypergraph, graph_pspecs
from repro.dist.sharding import Plan
from repro.utils import segops


def _graph_arg(d):
    """(inner DeviceHypergraph, storage-striped?) — the drivers accept
    replicated `DeviceHypergraph`s and memory-sharded `ShardedHypergraph`s
    interchangeably; the wrapper is the dispatch marker."""
    if isinstance(d, ShardedHypergraph):
        return d.g, True
    return d, False


def plan_axes(plan: Plan) -> tuple[str | None, str | None, int]:
    """(replica axis or None, pipeline-shard axis or None, shard count).

    The replica axis must be distinct from the pipeline-shard axis: the
    sharded pipelines psum partial sums over "model" assuming every shard
    holds the *same* move sequence, so replicas may never diverge along it.
    On a mesh whose only axis is "model" the driver therefore shards the
    pipelines and skips racing."""
    names = tuple(plan.mesh.axis_names)
    model_axis = ("model" if "model" in names
                  and plan.mesh.shape["model"] > 1 else None)
    nshards = plan.mesh.shape["model"] if model_axis else 1
    if "data" in names:
        data_axis = "data"
    else:
        cands = [a for a in names if a != "model"]
        data_axis = cands[0] if cands else None
    # a 1-replica axis cannot race: collapse it so the step skips the
    # per-repetition permutation + winner collectives entirely
    if data_axis is not None and plan.mesh.shape[data_axis] <= 1:
        data_axis = None
    return data_axis, model_axis, nshards


@functools.lru_cache(maxsize=None)
def _build_step(mesh, data_axis: str, model_axis: str | None, nshards: int,
                caps: Caps, kcap: int, params: RefineParams, race: bool,
                striped: bool = False):
    """One raced+sharded repetition, jitted; cached per static signature so
    the host-driven level loop compiles once per capacity bucket (exactly
    like `core.refine.refine_step`). ``striped``: the graph's pins-sized
    arrays enter as per-shard stripes over "model" (`dist.graph`)."""
    ctx = segops.ShardCtx(axis=model_axis, nshards=nshards,
                          graph_striped=striped and model_axis is not None)

    def body(d, parts, n_parts, key, enforce):
        ids = jnp.arange(caps.n, dtype=jnp.int32)
        if race and data_axis is not None:
            r = jax.lax.axis_index(data_axis)
            perm = jax.random.permutation(
                jax.random.fold_in(key, r), caps.n).astype(jnp.int32)
            # replica 0 races the identity (single-device) ordering
            tie_rank = jnp.where(r == 0, ids, perm)
        else:
            tie_rank = ids
        parts_new, gain, counts = refine_step_impl(
            d, parts, n_parts, caps, kcap, params, enforce, ctx, tie_rank)
        if data_axis is None:   # shard-only mesh: nothing to race
            return parts_new, gain, counts
        # race resolution: scalar gains all-gathered, winner's partition
        # vector and counts broadcast by psum of the masked values (no
        # parts gather)
        gains = jax.lax.all_gather(gain, data_axis)        # [n_replicas]
        best = jnp.argmax(gains).astype(jnp.int32)         # tie -> replica 0
        win = jax.lax.axis_index(data_axis) == best
        parts_out = jax.lax.psum(jnp.where(win, parts_new, 0), data_axis)
        counts_out = jax.lax.psum(jnp.where(win, counts, 0), data_axis)
        return parts_out, gains[best], counts_out

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(graph_pspecs(striped), P(), P(), P(), P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def refine_level(d, parts, n_parts, caps: Caps, kcap: int,
                 params: RefineParams, plan: Plan, *, race: bool = True,
                 seed: int = 0, log: list | None = None):
    """Drop-in for `core.refine.refine_level` on a mesh: Theta rounds, each
    an R-way replica race (R = data-axis size) over pipelines sharded
    M-way (M = model-axis size). `race=False` pins every replica to the
    identity tie-break — deterministic parity mode. ``d`` may be a
    replicated `DeviceHypergraph` or a memory-sharded
    `dist.graph.ShardedHypergraph` (racing replicas then share the one
    striped copy of the pins arrays).

    With ``use_kernels=True`` the gains/pins dispatches of
    ``core.refine`` stay live on the mesh: the `gains` kernel runs
    stripe-locally per shard (see `repro.kernels`), bit-identical to the
    single-device kernel path. Returns ``(parts, counters)`` as
    `core.refine.refine_level` does: the winning replicas' counts per
    repetition, reduced once per level (`refine.level_counters`; the
    kernel-branch counts are mesh-independent by the branch-parity
    invariant)."""
    d, striped = _graph_arg(d)
    data_axis, model_axis, nshards = plan_axes(plan)
    step = _build_step(plan.mesh, data_axis, model_axis, nshards,
                       caps, kcap, params, bool(race), striped)
    n_parts = jnp.asarray(n_parts, jnp.int32)
    # replicated on the mesh, as every repetition returns it: an
    # uncommitted first input would compile the step a second time
    parts = jax.device_put(parts, NamedSharding(plan.mesh, P()))
    key = jax.random.PRNGKey(seed)
    reps = []
    for rep in range(params.theta):
        enforce = jnp.asarray(rep >= params.theta // 2)
        parts, g, counts = step(d, parts, n_parts,
                                jax.random.fold_in(key, rep), enforce)
        reps.append(counts)
        if log is not None:
            log_rep(log, rep, g, counts, raced=bool(race))
    return parts, level_counters(reps)


@functools.lru_cache(maxsize=None)
def _build_coarsen_step(mesh, model_axis: str | None, nshards: int,
                        caps: Caps, cparams: CoarsenParams,
                        compensated: bool = False, striped: bool = False):
    """One sharded coarsening level (proposal + matching), jitted; cached
    per static signature like `_build_step`. ``compensated`` opts the eta /
    matching-sum0 float reductions into `ShardCtx.psum_compensated`
    (O(dense) traffic, ~1 ulp, not bit-identical — see segops)."""
    ctx = segops.ShardCtx(axis=model_axis, nshards=nshards,
                          compensated=compensated,
                          graph_striped=striped and model_axis is not None)

    def body(d):
        match, n_pairs, props = coarsen_step_impl(d, caps, cparams, ctx)
        return (match, n_pairs, props.n_pairs_live, props.n_nbr_entries,
                props.kernel_path_taken)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(graph_pspecs(striped),),
                       out_specs=(P(), P(), P(), P(), P()), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _build_contract(mesh, model_axis: str | None, nshards: int, caps: Caps,
                    striped: bool = False):
    ctx = segops.ShardCtx(axis=model_axis, nshards=nshards,
                          graph_striped=striped and model_axis is not None)

    def body(d, match):
        return contract_impl(d, match, caps, ctx)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(graph_pspecs(striped), P()),
                       out_specs=(graph_pspecs(striped), P()), check_vma=False)
    return jax.jit(fn)


def coarsen_level(d, caps: Caps, cparams: CoarsenParams, plan: Plan,
                  compensated: bool = False):
    """Drop-in for `core.coarsen.coarsen_step` on a mesh (without the
    proposals debug output): one coarsening level with the pairs/slot
    pipelines sharded over the plan's model axis. Deterministic — never
    raced — and bit-exact with the single-device step (see the module
    docstring for the psum / pmax / gather combine discipline).
    Returns (match[Ncap], n_matched_pairs).

    ``compensated=True`` trades that bit-exactness for traffic: the eta and
    matching-sum0 float reductions combine per-shard dense partials with a
    Neumaier-compensated psum (within ~1 ulp of the true sum) instead of
    gathering their lane columns in stripe order.

    With `use_kernels=True` the `pair_scores` dispatch of `coarsen.propose`
    stays live on the mesh: the kernel runs stripe-locally per shard and
    its per-row output is bit-identical to the single-device kernel path
    (see `repro.kernels` for the dispatch contract), so sharded-vs-single
    parity holds kernels-on against kernels-on. (Kernel eta sums in a
    different fp order than the segment pipeline, so kernels-on vs
    kernels-off remains an fp-tolerance comparison — same as on one
    device.)

    Returns ``(match, n_matched_pairs, (n_pairs_live, n_nbr_entries,
    kernel_path_taken))`` — the first two diagnostics feed the drivers'
    host-side capacity-overflow audit
    (`core.hypergraph.check_expansion_caps`); the trailing flag is 1 iff
    the pair_scores dispatch took the Pallas branch at this level."""
    d, striped = _graph_arg(d)
    _, model_axis, nshards = plan_axes(plan)
    step = _build_coarsen_step(plan.mesh, model_axis, nshards, caps, cparams,
                               bool(compensated), striped)
    match, n_pairs, pairs_live, nbr_entries, kernel_hit = step(d)
    return match, n_pairs, (pairs_live, nbr_entries, kernel_hit)


def contract_level(d, match, caps: Caps, plan: Plan):
    """Drop-in for `core.contract.contract` on a mesh: integer-only
    pipeline, bit-exact sharded contraction. Returns (d_coarse, gamma).
    With a memory-sharded input graph the coarse graph comes back
    memory-sharded too (its pins arrays are emitted as "model" stripes),
    so the level loop stays striped end-to-end."""
    d, striped = _graph_arg(d)
    _, model_axis, nshards = plan_axes(plan)
    fn = _build_contract(plan.mesh, model_axis, nshards, caps, striped)
    d2, gamma = fn(d, match)
    if striped:
        d2 = ShardedHypergraph(g=d2, nshards=nshards)
    return d2, gamma


def partition(hg, omega: int, delta: int, plan: Plan, *, race: bool = True,
              seed: int = 0, **kw):
    """Multi-level constrained partitioning with the whole V-cycle on the
    mesh: `core.partitioner.partition` with every coarsening level sharded
    (`coarsen_level`/`contract_level`) and every refinement level raced and
    sharded over `plan`."""
    from repro.core.partitioner import partition as _partition
    return _partition(hg, omega, delta, plan=plan, race=race,
                      race_seed=seed, **kw)
