"""Coarse hypergraph construction (paper Sec. V-E).

Clusters from `match` become coarse nodes; coarse h-edges are the images of
fine h-edges under gamma with pins deduplicated; a pin occurring as both src
and dst keeps only its dst role (paper: "duplicates ... are discarded from
src(.)/out(.)"), preserving inbound-set correctness and no-self-cycle.

GPU version: per-set hash-set dedup in shared+global memory, then
prefix-sum packing. TPU adaptation: stable multi-key sort + boundary flags +
prefix-sum compaction — identical result, deterministic, static shapes. The
repack into the src-first pin layout is a pair of segmented rank scans over
the sorted order (src rank / dst rank within each edge) plus a scatter to
``edge_off_new[e] + rank`` — the literal prefix-sum packing of the paper,
replacing a second full sort. Edge ids and weights are preserved
level-over-level (the edge *multiset* keeps its identity; only pin segments
shrink), exactly as in the paper.

Sharding (``ctx`` a ``segops.ShardCtx``, inside ``dist.partition``'s
shard_map): key construction runs on per-shard contiguous pin-lane stripes
(CSR row ids via stripe-local binary search), both key sorts run through
the distributed sample sort (``ctx.sort_by``: stripes in, stripes of the
globally sorted order out — only O(shards * samples) splitter keys are
gathered, the payload rides static-shape all_to_all exchanges), dedup /
per-edge boundary flags come from stripe-boundary-aware start flags, the
rank scans run stripe-local with cross-shard carries
(``sharded_segmented_scan``), and the packed pins / per-edge / per-node
counts and the rebuilt incidence arrays combine by psum of disjoint (or
integer) dense partials (``unstripe``). Every value in this pipeline is an
integer, so the sharded contraction is bit-exact with the single-device one
by construction — no float accumulation order to preserve.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hypergraph import Caps, DeviceHypergraph, NSENT
from repro.utils import segops

IMAX = jnp.int32(2**31 - 1)


def _role_key(is_dst: jax.Array) -> jax.Array:
    """Secondary sort key within an (edge, coarse-pin) duplicate group: dst
    (0) sorts before src (1), so the kept first occurrence carries the dst
    role whenever the merged pin had both (paper V-E: duplicates are
    discarded from src)."""
    return jnp.where(is_dst, 0, 1)


def contract_impl(d: DeviceHypergraph, match: jax.Array, caps: Caps,
                  ctx: segops.ShardCtx = segops.ShardCtx()):
    """Returns (coarse DeviceHypergraph, gamma[Ncap] old->coarse id). The
    phases run under the ``jax.named_scope``s clusters, pins and incidence,
    so their device ops carry stable names."""
    with jax.named_scope("clusters"):
        ids = jnp.arange(caps.n, dtype=jnp.int32)
        live = ids < d.n_nodes
        m_safe = jnp.clip(match, 0, caps.n - 1)
        paired = live & (match >= 0)
        rep = jnp.where(paired, jnp.minimum(ids, m_safe), ids)
        is_rep = live & (rep == ids)
        newid = (jnp.cumsum(is_rep.astype(jnp.int32)) - 1).astype(jnp.int32)
        gamma = jnp.where(live, newid[rep], -1)
        n_new = jnp.sum(is_rep.astype(jnp.int32))

        size_new = jax.ops.segment_sum(
            jnp.where(live, d.node_size, 0), jnp.where(live, gamma, caps.n),
            num_segments=caps.n + 1)[: caps.n].astype(jnp.int32)

    with jax.named_scope("pins"):
        # ---- coarse edge pins: map through gamma, dedup, src-first repack
        # key construction on this shard's contiguous pin-lane stripe
        t, t_in = ctx.lanes(caps.p)
        pin_live = t_in & (t < d.n_pins)
        e_of = ctx.rows(d.edge_off, t, caps.p, caps.e)
        e_safe = jnp.clip(e_of, 0, caps.e - 1)
        pin = jnp.clip(ctx.gread(d.edge_pins, t, pin_live, 0), 0, caps.n - 1)
        pprime = jnp.where(pin_live, gamma[pin], IMAX)
        rel = t - d.edge_off[e_safe]
        is_dst = pin_live & (rel >= d.edge_nsrc[e_safe])

        k_e = jnp.where(pin_live, e_of, IMAX)
        k_p = pprime
        k_r = _role_key(is_dst)
        # distributed sample sort: stripes in, stripes of the sorted order out
        # (only splitter samples gather); dedup flags are stripe-boundary-aware
        (se_l, sp_l, sr_l), _ = ctx.sort_by([k_e, k_p, k_r], [],
                                            striped_in=True, striped_out=True)
        starts_l = ctx.starts_from_sorted([se_l, sp_l])
        e_start_l = ctx.starts_from_sorted([se_l])
        keep_l = starts_l & (se_l != IMAX) & (sp_l != IMAX)
        # the first occurrence carries the merged role
        kept_dst_l = keep_l & (sr_l == 0)
        kept_src_l = keep_l & (sr_l == 1)

        # per-edge counts from the kept set (integers — psum is exact)
        seg_e = jnp.where(keep_l, se_l, caps.e)
        ones_l = jnp.ones(se_l.shape, jnp.int32)
        counts_e = ctx.psum(jax.ops.segment_sum(
            ones_l, seg_e, num_segments=caps.e + 1))[: caps.e]
        nsrc_new = ctx.psum(jax.ops.segment_sum(
            kept_src_l.astype(jnp.int32), seg_e,
            num_segments=caps.e + 1))[: caps.e]
        edge_off_new = segops.offsets_from_counts(counts_e).astype(jnp.int32)
        n_pins_new = edge_off_new[caps.e]

        # prefix-sum packing: src/dst rank within each edge via stripe-local
        # segmented scans with cross-shard carries, then a disjoint scatter to
        # edge_off_new[e] (+ nsrc for dst) + rank — src pins first, coarse-id
        # ascending within each role (the kept order is already p'-ascending)
        src_rank, _ = ctx.segmented_scan(kept_src_l.astype(jnp.int32),
                                         e_start_l)
        dst_rank, _ = ctx.segmented_scan(kept_dst_l.astype(jnp.int32),
                                         e_start_l)
        se_safe = jnp.clip(se_l, 0, caps.e - 1)
        pos = jnp.where(kept_src_l, edge_off_new[se_safe] + src_rank - 1,
                        edge_off_new[se_safe] + nsrc_new[se_safe]
                        + dst_rank - 1)
        striped = ctx.graph_striped and ctx.axis is not None
        if striped:
            # memory-sharded storage: reduce-scatter the packed pins so each
            # shard keeps exactly its lane stripe of the coarse graph — the
            # dense pins column never materializes replicated
            st = t.shape[0] * ctx.nshards
            pos = jnp.where(keep_l, pos, st).astype(jnp.int32)
            dense = (jnp.zeros((st + 1,), jnp.int32)
                     .at[pos].add(jnp.where(keep_l, sp_l, 0))[: st])
            pins_new = jnp.where(t < n_pins_new, ctx.psum_stripe(dense), NSENT)
        else:
            pos = jnp.where(keep_l, pos, caps.p).astype(jnp.int32)
            pins_dense = ctx.psum(
                jnp.zeros((caps.p + 1,), jnp.int32)
                .at[pos].add(jnp.where(keep_l, sp_l, 0))[: caps.p])
            slot = jnp.arange(caps.p, dtype=jnp.int32)
            pins_new = jnp.where(slot < n_pins_new, pins_dense, NSENT)

    with jax.named_scope("incidence"):
        # ---- incidence rebuild (inbound first) ---------------------------
        t2_live = t_in & (t < n_pins_new)
        e2 = ctx.rows(edge_off_new, t, caps.p, caps.e)
        e2_safe = jnp.clip(e2, 0, caps.e - 1)
        rel2 = t - edge_off_new[e2_safe]
        isdst2 = t2_live & (rel2 >= nsrc_new[e2_safe])
        node2 = ctx.gread(pins_new, t, t2_live, IMAX)
        inkey = jnp.where(isdst2, 0, 1)  # inbound edges first
        key_e = jnp.where(t2_live, e2, IMAX)
        (sn2_l, sk2_l, se2_l), (sin2_l,) = ctx.sort_by(
            [node2, inkey, key_e], [isdst2.astype(jnp.int32)],
            striped_in=True, striped_out=True)
        # incidence rebuild from the sorted stripes: with memory-sharded
        # storage the sorted stripes already ARE the new incidence layout, so
        # each shard simply keeps its stripe; otherwise the replicated arrays
        # rebuild by psum of disjoint stripe scatters (`unstripe`) — integer,
        # exact either way
        ne_stripe = jnp.where(sn2_l != IMAX, se2_l, NSENT)
        ni_stripe = (sin2_l == 1) & (sn2_l != IMAX)
        if striped:
            node_edges_new, node_is_in_new = ne_stripe, ni_stripe
        else:
            node_edges_new = ctx.unstripe(ne_stripe)[: caps.p]
            node_is_in_new = ctx.unstripe(ni_stripe)[: caps.p]
        segn = jnp.where(sn2_l != IMAX, sn2_l, caps.n)
        counts_n = ctx.psum(jax.ops.segment_sum(
            jnp.ones(sn2_l.shape, jnp.int32), segn,
            num_segments=caps.n + 1))[: caps.n]
        nin_new = ctx.psum(jax.ops.segment_sum(
            ((sin2_l == 1) & (sn2_l != IMAX)).astype(jnp.int32), segn,
            num_segments=caps.n + 1))[: caps.n]
        node_off_new = segops.offsets_from_counts(counts_n).astype(jnp.int32)

    d_new = DeviceHypergraph(
        edge_off=edge_off_new,
        edge_pins=pins_new.astype(jnp.int32),
        edge_nsrc=nsrc_new,
        edge_w=d.edge_w,
        node_off=node_off_new,
        node_edges=node_edges_new.astype(jnp.int32),
        node_is_in=node_is_in_new,
        node_nin=nin_new,
        node_size=size_new,
        n_nodes=n_new.astype(jnp.int32),
        n_edges=d.n_edges,
        n_pins=n_pins_new.astype(jnp.int32),
    )
    return d_new, gamma


@partial(jax.jit, static_argnames=("caps",))
def contract(d: DeviceHypergraph, match: jax.Array, caps: Caps):
    """Single-device entry point; `dist.partition.contract_level` runs the
    same `contract_impl` under shard_map with a mesh-axis ctx."""
    return contract_impl(d, match, caps)
