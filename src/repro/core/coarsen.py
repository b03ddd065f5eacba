"""Constraint-aware coarsening: candidate pairs proposal (paper Sec. V-C).

Per node n we build the neighbor histogram

    eta(n, m) = sum_{e in I(n), m in e} w(e)/|e|                    (Eq. 5)

and — inline, in the same pass, exactly like the paper's in-histogram
counter (Fig. 3) — the inbound-set intersection

    inter(n, m) = |{e : n, m in dst(e)}|

so the union-size constraint check is `|in(n)|+|in(m)|-inter(n,m) <= Delta`
with no extra traversal. On GPU the histogram lives in shared memory and
pins binary-search their bin; here the histogram is *the materialized
neighborhood segment itself* (slots sorted by id), the binary search is a
vectorized segmented search, and the accumulation is a segment-sum over the
flat pair expansion. The Pallas kernel `repro.kernels.pair_scores` provides
the TPU-tiled equivalent of the same computation.

Candidate quality mechanisms reproduced from the paper: symmetric
deterministic noise capped at 10% of mean edge weight; top-Pi candidates per
node (Pi proposal graphs / matching rounds); best-effort pairing of nodes
with no valid candidates (size-sorted, union size overestimated by sums).

Every pins/pairs-sized stage threads an optional `segops.ShardCtx` (mirror
of `core.refine`): inside `dist.partition`'s shard_map the pair expansion,
the neighborhood binary searches and the Pi-round candidate argmaxes run on
one contiguous lane stripe per device. Integer partials (inter, matching
counts) combine with psum, per-node (value, id) claims with an exact
lexicographic pmax, and float partials (eta) gather their lane columns in
stripe order so the accumulation order — and hence every last bit — matches
the single-device path. With the default ctx everything below is the exact
single-device computation.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hypergraph import (Caps, DeviceHypergraph, Neighborhoods,
                                   PairExpansion, NSENT)
from repro.core.matching import match_pseudoforest
from repro.utils import segops
from repro.utils.hashing import pair_noise

NEG = jnp.float32(-jnp.inf)


@dataclasses.dataclass(frozen=True)
class CoarsenParams:
    omega: int            # max cluster/partition size
    delta: int            # max distinct inbound h-edges
    n_cands: int = 4      # Pi
    noise_frac: float = 0.1
    use_kernels: bool = False  # route scoring through the Pallas kernels
    matching: str = "exact"    # "exact" DP | "greedy" (ablation, [22])

    def __post_init__(self):
        if self.matching not in ("exact", "greedy"):
            raise ValueError(
                "CoarsenParams.matching must be 'exact' or 'greedy', got "
                f"{self.matching!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Proposals:
    cand_ids: jax.Array     # [Pi, Ncap] neighbor id or -1
    cand_scores: jax.Array  # [Pi, Ncap]
    eta: jax.Array          # [NBcap] histogram values (for tests/ablation)
    inter: jax.Array        # [NBcap]
    valid_slot: jax.Array   # [NBcap]
    # live-vs-capacity diagnostics for the drivers' host-side overflow
    # audit (`hypergraph.check_expansion_caps`): the true ordered-pin-pair
    # expansion size and the deduplicated neighborhood entry count — the
    # device pipelines silently drop out-of-capacity lanes, so exceeding
    # `caps.pairs` / `caps.nbrs` must raise host-side, not mis-partition.
    n_pairs_live: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))  # scalar
    n_nbr_entries: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))  # scalar
    # live neighbour slots refused as merge candidates, each counted for
    # the first limit it breaks: merged size above Omega, else inbound
    # union above Delta (which limit binds the coarsening; telemetry only)
    n_refused_omega: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))  # scalar
    n_refused_delta: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))  # scalar
    # 1 iff the use_kernels dispatch took the Pallas branch (0 on the
    # segment path or with use_kernels=False) — surfaces silent fallbacks
    # to tests/benchmarks via `PartitionResult.kernel_path`
    kernel_path_taken: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))  # scalar


def score_slots(d: DeviceHypergraph, nbrs: Neighborhoods,
                pairs: PairExpansion, caps: Caps,
                ctx: segops.ShardCtx = segops.ShardCtx()):
    """eta + inter accumulated over materialized neighbor slots.

    ``pairs`` may be one shard's lane stripe (``build_pairs`` with
    ``idx=ctx.lanes(caps.pairs)``): the binary searches run stripe-local;
    the integer ``inter`` partials psum exactly, while the float ``eta``
    lanes gather in stripe order — the global lane order — so the scatter
    accumulation order (and hence every bit) matches one device."""
    n_safe = jnp.clip(pairs.n, 0, caps.n - 1)
    lo = nbrs.off[n_safe]
    hi = nbrs.off[jnp.clip(pairs.n + 1, 0, caps.n)]
    iters = max(1, math.ceil(math.log2(caps.nbrs + 1)) + 1)
    slot = segops.searchsorted_segmented(nbrs.ids, lo, hi, pairs.m, iters)
    slot = jnp.where(pairs.valid, slot, caps.nbrs)
    if ctx.compensated:
        # opt-in O(dense) combine: Neumaier-compensated psum of per-shard
        # partials (~1 ulp of the true sum, not bit-identical to one device)
        eta = ctx.psum_compensated(jax.ops.segment_sum(
            pairs.w_norm, slot, num_segments=caps.nbrs + 1)[: caps.nbrs])
    else:
        eta = jax.ops.segment_sum(ctx.gather(pairs.w_norm), ctx.gather(slot),
                                  num_segments=caps.nbrs + 1)[: caps.nbrs]
    inter = ctx.psum(jax.ops.segment_sum(
        pairs.both_dst.astype(jnp.int32), slot,
        num_segments=caps.nbrs + 1))[: caps.nbrs]
    return eta, inter


def propose(d: DeviceHypergraph, nbrs: Neighborhoods, pairs: PairExpansion,
            caps: Caps, params: CoarsenParams,
            ctx: segops.ShardCtx = segops.ShardCtx()) -> Proposals:
    if params.use_kernels:
        from repro.kernels.pair_scores import ops as ps_ops
        # tile bounds are level-0 derived; guard + fall back (see ops.py).
        # The predicate is replicated and mesh-independent, so every shard
        # takes the same branch and the branch matches the single-device
        # run — required by the race=False parity contract.
        fits = ps_ops.fits_kernel(d, nbrs, pairs, caps, ctx)
        eta, inter = jax.lax.cond(
            fits,
            lambda: ps_ops.score_slots_kernel(d, nbrs, pairs, caps, ctx),
            lambda: score_slots(d, nbrs, pairs, caps, ctx))
        kernel_taken = fits.astype(jnp.int32)
    else:
        eta, inter = score_slots(d, nbrs, pairs, caps, ctx)
        kernel_taken = jnp.int32(0)

    owner = segops.rows_from_offsets(nbrs.off, caps.nbrs, caps.n)
    m = nbrs.ids
    entry_live = (m != NSENT) & (owner < caps.n)
    owner_safe = jnp.clip(owner, 0, caps.n - 1)
    m_safe = jnp.clip(m, 0, caps.n - 1)

    mean_w = jnp.sum(d.edge_w) / jnp.maximum(d.n_edges, 1)
    noise = pair_noise(owner_safe, m_safe, 1.0) * (params.noise_frac * mean_w)
    eta_n = eta + jnp.where(entry_live, noise, 0.0)

    size_ok = d.node_size[owner_safe] + d.node_size[m_safe] <= params.omega
    union = d.node_nin[owner_safe] + d.node_nin[m_safe] - inter
    inbound_ok = union <= params.delta
    valid_slot = entry_live & size_ok & inbound_ok
    # which limit refuses each live slot, the first it breaks; the slot
    # arrays are replicated on every shard, so these sums are already the
    # global counts and need no collective
    n_refused_omega = jnp.sum(entry_live & ~size_ok, dtype=jnp.int32)
    n_refused_delta = jnp.sum(entry_live & size_ok & ~inbound_ok,
                              dtype=jnp.int32)

    value = jnp.where(valid_slot, eta_n, NEG)

    # Pi candidate rounds on lane-local slot stripes: each shard argmaxes
    # its contiguous stripe of the slot space, winners combine with the
    # exact cross-shard lexicographic (value, slot-id) pmax, and the shard
    # owning the winning slot retires it for the next round.
    sl, sl_ok = ctx.lanes(caps.nbrs)
    owner_l = owner_safe[jnp.clip(sl, 0, caps.nbrs - 1)]
    value_l = ctx.take(value, sl, sl_ok, NEG)
    per = sl.shape[0]

    cand_ids, cand_scores = [], []
    for _ in range(params.n_cands):
        mx_l, arg_l = segops.segment_argmax(
            value_l, sl, owner_l, caps.n, valid=value_l > NEG)
        mx, arg_slot = ctx.pmax_pair(mx_l, arg_l)
        got = (arg_slot >= 0) & ~jnp.isneginf(mx)
        cid = jnp.where(got, m[jnp.clip(arg_slot, 0, caps.nbrs - 1)], -1)
        cand_ids.append(cid)
        cand_scores.append(jnp.where(got, mx, 0.0))
        loc = arg_slot - sl[0]
        value_l = value_l.at[jnp.where(got & (loc >= 0) & (loc < per),
                                       loc, per)].set(NEG, mode="drop")

    return Proposals(cand_ids=jnp.stack(cand_ids),
                     cand_scores=jnp.stack(cand_scores),
                     eta=eta_n, inter=inter, valid_slot=valid_slot,
                     n_refused_omega=n_refused_omega,
                     n_refused_delta=n_refused_delta,
                     kernel_path_taken=kernel_taken)


def run_matching_rounds(props: Proposals, d: DeviceHypergraph, caps: Caps,
                        params: CoarsenParams,
                        ctx: segops.ShardCtx = segops.ShardCtx()) -> jax.Array:
    """Pi rounds of exact matching; matched nodes leave subsequent graphs."""
    ids = jnp.arange(caps.n, dtype=jnp.int32)
    live0 = ids < d.n_nodes
    match = jnp.full((caps.n,), -1, jnp.int32)

    for pi in range(params.n_cands):
        unmatched = live0 & (match < 0)
        tgt = props.cand_ids[pi]
        t_safe = jnp.clip(tgt, 0, caps.n - 1)
        tgt = jnp.where(unmatched & (tgt >= 0) & (match[t_safe] < 0), tgt, -1)
        if params.matching == "greedy":
            # ablation: prototype heuristic [22] — only mutual targets pair
            mutual = (tgt >= 0) & (tgt[jnp.clip(tgt, 0, caps.n - 1)] == ids)
            m_round = jnp.where(mutual, tgt, -1)
        else:
            m_round = match_pseudoforest(tgt, props.cand_scores[pi],
                                         unmatched, ctx)
        match = jnp.where((match < 0) & (m_round >= 0), m_round, match)
    return match


def pair_isolated(match: jax.Array, props: Proposals, d: DeviceHypergraph,
                  caps: Caps, params: CoarsenParams) -> jax.Array:
    """Best-effort pairing of nodes left with no valid candidates: sort by
    (size, id), pair adjacent entries when within constraints; inbound union
    overestimated by |in(n)|+|in(m)| (paper Sec. V-C, last mechanism)."""
    ids = jnp.arange(caps.n, dtype=jnp.int32)
    live = ids < d.n_nodes
    lonely = live & (match < 0) & (props.cand_ids[0] < 0)
    key = jnp.where(lonely, d.node_size, jnp.int32(2**30))
    (_, _), (perm,) = segops.sort_by([key, ids], [ids])
    npairs = caps.n // 2  # odd capacity: the last sorted entry stays single
    a = perm[0: 2 * npairs: 2]
    b = perm[1: 2 * npairs: 2]
    ok = (lonely[a] & lonely[b]
          & (d.node_size[a] + d.node_size[b] <= params.omega)
          & (d.node_nin[a] + d.node_nin[b] <= params.delta))
    match = match.at[jnp.where(ok, a, caps.n)].set(b, mode="drop")
    match = match.at[jnp.where(ok, b, caps.n)].set(a, mode="drop")
    return match


def coarsen_step_impl(d: DeviceHypergraph, caps: Caps, params: CoarsenParams,
                      ctx: segops.ShardCtx = segops.ShardCtx()):
    """One full coarsening level: neighbors -> proposals -> matching.

    Single source of truth for the jitted single-device ``coarsen_step``
    and ``dist.partition.coarsen_level``'s shard_map'd body (``ctx`` stripes
    the pairs/slot pipelines; the isolated-node pairing sort stays
    replicated — its inputs are node-sized and already replicated). The
    phases run under the ``jax.named_scope``s pairs, neighbors, propose and
    matching, so their device ops carry stable names."""
    from repro.core.hypergraph import build_neighbors, build_pairs

    with jax.named_scope("pairs"):
        pidx, pidx_ok = ctx.lanes(caps.pairs)
        pairs = build_pairs(d, caps, idx=pidx, idx_ok=pidx_ok, ctx=ctx)
    with jax.named_scope("neighbors"):
        nbrs = build_neighbors(pairs, d, caps, ctx)
    with jax.named_scope("propose"):
        props = propose(d, nbrs, pairs, caps, params, ctx)
    with jax.named_scope("matching"):
        match = run_matching_rounds(props, d, caps, params, ctx)
        match = pair_isolated(match, props, d, caps, params)
    n_pairs = jnp.sum((match >= 0) & (jnp.arange(caps.n) < d.n_nodes)) // 2
    props = dataclasses.replace(props, n_pairs_live=pairs.n_pairs,
                                n_nbr_entries=nbrs.n_entries)
    return match, n_pairs, props


@partial(jax.jit, static_argnames=("caps", "params"))
def coarsen_step(d: DeviceHypergraph, caps: Caps, params: CoarsenParams):
    """Returns (match[Ncap], n_matched_pairs, proposals) — contraction
    happens in `repro.core.contract`."""
    return coarsen_step_impl(d, caps, params)
