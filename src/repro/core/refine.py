"""Uncoarsening refinement (paper Sec. VI): FM-style moves applied
simultaneously via gain-ranked chains + events-based constraint validation.

Pipeline per repetition (Theta total, default 16):

  1. pins(p,e) matrix precomputation (Sec. VI-B, Fig. 2 right)
  2. in-isolation move proposal from Eq. 13 (gain = saving - loss)
  3. moves chained into paths/cycles by a greedy windowed path cover
     (Sec. VI-C, Fig. 5): grade = gain - alpha*|size dif| - beta*|in dif|
  4. in-sequence gain re-derivation (Eq. 14-15) over the pair expansion
  5. sparse events: size + inbound-set deltas, sorted, segment-prefix-summed;
     per-move active-violation count; apply the max-cumulative-gain valid
     prefix (Sec. VI-D, Fig. 6)

CUDA -> TPU mapping: warp-per-node gain loops become segment reductions /
the Pallas `gains` kernel; CUB sort+scan become `lax.sort` (multi-key) +
segmented `associative_scan` — on a mesh, the distributed sample sort
(`ShardCtx.sort_by`) + stripe-local scans with cross-shard carries; atomic
grade claims become segment-argmax with id tie-breaks.

Every pins/pairs-sized stage threads an optional `segops.ShardCtx`: with a
mesh axis set (inside `dist.partition`'s shard_map) the stage processes one
contiguous lane stripe per device and combines dense segment outputs with
psum; with the default ctx it is the exact single-device computation. Chain
construction additionally takes a `tie_rank` permutation so racing replicas
explore distinct (equally greedy) move orderings. The first half of the Theta repetitions may propose
size-violating moves, the second half enforces size feasibility in the
proposal — final validity is always enforced by the events check, with
violations permitted *inside* the sequence (only the cut point must be
globally valid), exactly as in the paper.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hypergraph import Caps, DeviceHypergraph, build_pairs
from repro.utils import segops

IMAX = jnp.int32(2**31 - 1)
NEG = jnp.float32(-jnp.inf)


@dataclasses.dataclass(frozen=True)
class RefineParams:
    omega: int
    delta: int
    theta: int = 16           # repetitions per level
    window: int = 256         # successor window (paper: 256)
    chain_rounds: int = 16    # chaining rounds (paper: up to 16)
    alpha: float = 1e-6       # size-difference grade weight
    beta: float = 1e-7        # inbound-size-difference grade weight
    include_zero_gain: bool = True  # allow 0-gain proposals (enables swaps)
    use_kernels: bool = False


# ---------------------------------------------------------------------------
# 1. pins matrix
# ---------------------------------------------------------------------------
def pins_matrix(d: DeviceHypergraph, parts: jax.Array, caps: Caps, kcap: int,
                ctx: segops.ShardCtx = segops.ShardCtx()):
    """pins[p,e] (all pins) and pins_in[p,e] (dst pins only), [kcap, Ecap].

    Sharded mode (``ctx.axis`` set, inside shard_map): each device counts
    only its contiguous stripe of pin lanes and the dense [kcap, Ecap]
    matrices are psum-combined — the all-gather-free segment reduction."""
    t, in_rng = ctx.lanes(caps.p)
    live = in_rng & (t < d.n_pins)
    e_of = ctx.rows(d.edge_off, t, caps.p, caps.e)
    e_safe = jnp.clip(e_of, 0, caps.e - 1)
    pin = jnp.clip(ctx.gread(d.edge_pins, t, live, 0), 0, caps.n - 1)
    p_of = jnp.where(live, parts[pin], kcap)
    rel = t - d.edge_off[e_safe]
    is_dst = live & (rel >= d.edge_nsrc[e_safe])
    flat = jnp.where(live, p_of * caps.e + e_safe, kcap * caps.e)
    ones = jnp.ones(t.shape, jnp.int32)
    pins = jax.ops.segment_sum(ones, flat, num_segments=kcap * caps.e + 1)
    pins = ctx.psum(pins[:-1]).reshape(kcap, caps.e)
    pins_in = jax.ops.segment_sum(is_dst.astype(jnp.int32), flat,
                                  num_segments=kcap * caps.e + 1)
    pins_in = ctx.psum(pins_in[:-1]).reshape(kcap, caps.e)
    return pins, pins_in


def partition_sizes(d: DeviceHypergraph, parts: jax.Array, caps: Caps, kcap: int):
    ids = jnp.arange(caps.n, dtype=jnp.int32)
    live = ids < d.n_nodes
    return jax.ops.segment_sum(jnp.where(live, d.node_size, 0),
                               jnp.where(live, parts, kcap),
                               num_segments=kcap + 1)[:kcap]


# ---------------------------------------------------------------------------
# 2. move proposal (Eq. 13)
# ---------------------------------------------------------------------------
def propose_moves(d: DeviceHypergraph, parts: jax.Array, pins: jax.Array,
                  caps: Caps, kcap: int, params: RefineParams,
                  enforce_size: jax.Array, n_parts: jax.Array,
                  ctx: segops.ShardCtx = segops.ShardCtx()):
    """Returns (move_to[Ncap] or -1, gain_iso[Ncap], saving[Ncap],
    kernel_taken) — the trailing scalar is 1 iff the conn_w dispatch took
    the Pallas `gains` branch (0 on the segment path)."""
    t, in_rng = ctx.lanes(caps.p)
    live = in_rng & (t < d.n_pins)
    n_of = ctx.rows(d.node_off, t, caps.p, caps.n)
    n_safe = jnp.clip(n_of, 0, caps.n - 1)
    e = jnp.clip(ctx.gread(d.node_edges, t, live, 0), 0, caps.e - 1)
    w = jnp.where(live, d.edge_w[e], 0.0)
    p_n = parts[n_safe]

    pins_own = pins[p_n, e]
    saving = ctx.psum(jax.ops.segment_sum(
        jnp.where(live & (pins_own == 1), w, 0.0),
        jnp.where(live, n_of, caps.n), num_segments=caps.n + 1)[: caps.n])
    w_tot = ctx.psum(jax.ops.segment_sum(
        w, jnp.where(live, n_of, caps.n),
        num_segments=caps.n + 1)[: caps.n])

    def _conn_segments():
        # conn_w[n, p] = sum_{e in I(n)} w(e) * [pins(p,e) > 0]
        contrib = jnp.where(live, w, 0.0)[:, None] * (pins[:, e].T > 0)
        return ctx.psum(jax.ops.segment_sum(
            contrib, jnp.where(live, n_of, caps.n),
            num_segments=caps.n + 1)[: caps.n])

    if params.use_kernels:
        from repro.kernels.gains import ops as g_ops
        # replicated mesh-independent predicate: every shard (and the
        # single-device run) takes the same branch — see repro.kernels
        fits = g_ops.fits_kernel(d, caps)
        conn_w = jax.lax.cond(
            fits,
            lambda: g_ops.conn_weights(d, parts, pins, caps, kcap, ctx),
            _conn_segments)
        kernel_taken = fits.astype(jnp.int32)
    else:
        conn_w = _conn_segments()
        kernel_taken = jnp.int32(0)

    ids = jnp.arange(caps.n, dtype=jnp.int32)
    node_live = ids < d.n_nodes
    # gain(n,p) = saving - (w_tot - conn_w) ; exclude own partition
    gain_all = saving[:, None] - w_tot[:, None] + conn_w
    col = jnp.arange(kcap, dtype=jnp.int32)[None, :]
    mask = (col != parts[:, None]) & (col < n_parts)
    psz = partition_sizes(d, parts, caps, kcap)
    fits = psz[None, :] + d.node_size[:, None] <= params.omega
    mask = mask & jnp.where(enforce_size, fits, True)
    gain_all = jnp.where(mask, gain_all, NEG)

    # paper tie-break: max_id argmax over partitions
    mx = jnp.max(gain_all, axis=1)
    best_p = jnp.max(jnp.where(gain_all == mx[:, None], col, -1), axis=1)
    best_g = mx
    ok = node_live & (best_p >= 0) & ~jnp.isneginf(best_g)
    ok = ok & ((best_g >= 0.0) if params.include_zero_gain else (best_g > 0.0))
    move_to = jnp.where(ok, best_p.astype(jnp.int32), -1)
    return move_to, jnp.where(ok, best_g, 0.0), saving, kernel_taken


# ---------------------------------------------------------------------------
# 3. chain construction (Sec. VI-C)
# ---------------------------------------------------------------------------
def build_sequence(d: DeviceHypergraph, parts: jax.Array, move_to: jax.Array,
                   gain: jax.Array, caps: Caps, kcap: int,
                   params: RefineParams, tie_rank: jax.Array | None = None,
                   with_aux: bool = False,
                   ctx: segops.ShardCtx = segops.ShardCtx()):
    """Orders moves into gain-ranked chains; returns seq[Ncap] (IMAX for
    non-movers) and n_movers.

    ``tie_rank`` (a permutation of node ids, default identity) replaces the
    node id wherever it only breaks ties — the sort keys, the successor-claim
    argmax, and the cycle-cut anchor. Distinct permutations give the
    replica-racing mode of ``dist.partition`` distinct (equally greedy)
    chains per device; the identity reproduces the single-device sequence
    bit-for-bit. ``with_aux`` additionally returns the pred/head arrays for
    the oracle/property tests. The mover and chain-head orderings run
    through ``ctx.sort_by`` (replicated in/out — the windowed candidate
    lookup needs the whole sorted order), so on a mesh the sort work
    distributes while the result stays replicated and bit-identical."""
    ids = jnp.arange(caps.n, dtype=jnp.int32)
    rank = ids if tie_rank is None else tie_rank
    mover = move_to >= 0
    ps = jnp.where(mover, parts, kcap)
    pd = jnp.where(mover, move_to, kcap)

    # sort movers by (ps, -gain, rank): per-source-partition gain-descending
    gkey = jnp.where(mover, -gain, jnp.float32(jnp.inf))
    (_, _, _), (order,) = ctx.sort_by([ps, gkey, rank], [ids])
    # segment start offset per partition
    cnt_p = jax.ops.segment_sum(jnp.ones((caps.n,), jnp.int32), ps,
                                num_segments=kcap + 1)[:kcap]
    seg_off = segops.offsets_from_counts(cnt_p)[:-1]  # [kcap]

    pred = jnp.full((caps.n,), -1, jnp.int32)
    has_succ = jnp.zeros((caps.n,), bool)
    W = params.window

    for _ in range(params.chain_rounds):
        free = mover & ~has_succ
        # windowed candidates in the pd-segment of the sorted move list
        base = seg_off[jnp.clip(pd, 0, kcap - 1)]
        end = base + cnt_p[jnp.clip(pd, 0, kcap - 1)]
        cand_pos = base[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        in_seg = cand_pos < end[:, None]
        cand = order[jnp.clip(cand_pos, 0, caps.n - 1)]          # [Ncap, W]
        c_ok = (in_seg & free[:, None] & mover[cand] & (pred[cand] < 0)
                & (cand != ids[:, None]))
        grade = (gain[cand]
                 - params.alpha * jnp.abs(d.node_size[:, None]
                                          - d.node_size[cand]).astype(jnp.float32)
                 - params.beta * jnp.abs(d.node_nin[:, None]
                                         - d.node_nin[cand]).astype(jnp.float32))
        grade = jnp.where(c_ok, grade, NEG)
        gmax = jnp.max(grade, axis=1)
        pick = jnp.max(jnp.where(grade == gmax[:, None], cand, -1), axis=1)
        want = free & (pick >= 0) & ~jnp.isneginf(gmax)
        # conflicts: parallel max on (grade, proposer rank) per successor
        # (paper's atomic lexicographic max; rank==id unless racing)
        succ_seg = jnp.where(want, pick, caps.n)
        _, winner = segops.segment_argmax(gmax, rank, succ_seg, caps.n + 1,
                                          valid=want)
        winner = winner[: caps.n]
        got = want & (winner[jnp.clip(pick, 0, caps.n - 1)] == rank)
        pred = pred.at[jnp.where(got, pick, caps.n)].set(ids, mode="drop")
        has_succ = has_succ | got

    # --- resolve chains: cut cycles at their min-rank node -----------------
    K = max(1, math.ceil(math.log2(caps.n + 1)) + 1)
    ptr = pred
    minacc = jnp.where(ptr >= 0,
                       jnp.minimum(rank, rank[jnp.clip(ptr, 0, caps.n - 1)]),
                       rank)
    for _ in range(K):
        p_safe = jnp.clip(ptr, 0, caps.n - 1)
        minacc = jnp.where(ptr >= 0, jnp.minimum(minacc, minacc[p_safe]), minacc)
        ptr = jnp.where(ptr >= 0, ptr[p_safe], -1)
    on_cycle = ptr >= 0  # pred-chain never terminated
    cyc_head = on_cycle & (minacc == rank)
    pred = jnp.where(cyc_head, -1, pred)

    # --- position within chain + chain head via pointer doubling ----------
    ptr = pred
    dist = jnp.where(ptr >= 0, 1, 0).astype(jnp.int32)
    head = jnp.where(ptr >= 0, ptr, ids)
    for _ in range(K):
        p_safe = jnp.clip(ptr, 0, caps.n - 1)
        dist = jnp.where(ptr >= 0, dist + dist[p_safe], dist)
        head = jnp.where(ptr >= 0, head[p_safe], head)
        ptr = jnp.where(ptr >= 0, ptr[p_safe], -1)

    # --- rank chains by total gain (desc), concatenate ---------------------
    seg_head = jnp.where(mover, head, caps.n)
    chain_gain = jax.ops.segment_sum(jnp.where(mover, gain, 0.0), seg_head,
                                     num_segments=caps.n + 1)[: caps.n]
    chain_len = jax.ops.segment_sum(jnp.ones((caps.n,), jnp.int32), seg_head,
                                    num_segments=caps.n + 1)[: caps.n]
    is_head = mover & (head == ids)
    hkey = jnp.where(is_head, -chain_gain, jnp.float32(jnp.inf))
    (_, _), (horder,) = ctx.sort_by([hkey, rank], [ids])
    # chain start offsets in ranked order
    rlen = jnp.where(is_head[horder], chain_len[horder], 0)
    roff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(rlen)[:-1].astype(jnp.int32)])
    chain_start = jnp.zeros((caps.n,), jnp.int32).at[horder].set(roff)
    seq = jnp.where(mover, chain_start[jnp.clip(head, 0, caps.n - 1)] + dist,
                    IMAX)
    n_movers = jnp.sum(mover.astype(jnp.int32))
    if with_aux:
        return seq, n_movers, dict(pred=pred, head=head, dist=dist,
                                   cyc_head=cyc_head)
    return seq, n_movers


# ---------------------------------------------------------------------------
# 4. in-sequence gains (Eq. 14 / 15)
# ---------------------------------------------------------------------------
def inseq_gains(d: DeviceHypergraph, parts: jax.Array, pins: jax.Array,
                move_to: jax.Array, gain_iso: jax.Array, seq: jax.Array,
                caps: Caps, kcap: int,
                ctx: segops.ShardCtx = segops.ShardCtx()):
    pidx, p_ok = ctx.lanes(caps.pairs)
    pairs = build_pairs(d, caps, idx=pidx, idx_ok=p_ok, ctx=ctx)
    n = jnp.clip(pairs.n, 0, caps.n - 1)
    m = jnp.clip(pairs.m, 0, caps.n - 1)
    e = jnp.clip(pairs.edge, 0, caps.e - 1)
    mover_n = pairs.valid & (move_to[n] >= 0)
    mover_m = pairs.valid & (move_to[m] >= 0)
    before = mover_n & mover_m & (seq[m] < seq[n])

    ps_n, pd_n = parts[n], jnp.clip(move_to[n], 0, kcap - 1)
    ps_m, pd_m = parts[m], jnp.clip(move_to[m], 0, kcap - 1)

    # per-(n,e) counts, keyed by incidence slot. The count vectors are only
    # ever read at this shard's own slot lanes, so combine the pair-shard
    # partials with a reduce-scatter over the lane stripes (1/nshards the
    # payload of a full psum). Lane stripes are ceil-divided, so the dense
    # vector is padded to nshards * lanes-per-shard; the sentinel bucket
    # sits past that.
    t, t_ok = ctx.lanes(caps.p)
    stripe_total = t.shape[0] * ctx.nshards
    seg = jnp.where(mover_n, pairs.slot_n, stripe_total)

    def cnt(cond):
        return ctx.psum_stripe(jax.ops.segment_sum(
            jnp.where(before & cond, 1, 0), seg,
            num_segments=stripe_total + 1)[: stripe_total])

    a_pd = cnt(pd_n == ps_m)          # m leaving n's destination
    b_pd = cnt(pd_n == pd_m)          # m also entering it
    a_ps = cnt(ps_n == ps_m)          # m also leaving n's source
    b_ps = cnt(ps_n == pd_m)          # m entering it

    # per-(n, e) evaluation at each live incidence slot (slot lanes sharded)
    slot_live = t_ok & (t < d.n_pins)
    # slot_n indexes edge_pins: node at that slot, edge via rows
    e_slot = ctx.rows(d.edge_off, t, caps.p, caps.e)
    e_slot = jnp.clip(e_slot, 0, caps.e - 1)
    n_slot = jnp.clip(ctx.gread(d.edge_pins, t, slot_live, 0), 0, caps.n - 1)
    is_mover = slot_live & (move_to[n_slot] >= 0)
    psn = parts[n_slot]
    pdn = jnp.clip(move_to[n_slot], 0, kcap - 1)
    w = d.edge_w[e_slot]
    pins_pd = pins[pdn, e_slot]
    pins_ps = pins[psn, e_slot]

    # Exact in-sequence correction. Paper Eq. 14/15 express the four
    # transition cases as two OR-ed conditions adjusting by +-w once; when
    # both clauses of one equation hold simultaneously (e.g. the move both
    # loses its isolation saving AND creates a new cut on the same h-edge)
    # the OR under-counts by w. We use the equivalent exact before/after
    # form, which reduces to Eq. 14/15 whenever a single clause fires
    # (verified against both a literal Eq. 14/15 oracle and brute-force
    # connectivity deltas in tests/test_refine.py).
    saving_iso = pins_ps == 1
    saving_now = (pins_ps - a_ps + b_ps) == 1
    loss_iso = pins_pd == 0
    loss_now = (pins_pd - a_pd + b_pd) == 0
    adj = jnp.where(
        is_mover,
        w * ((saving_now.astype(jnp.float32) - saving_iso.astype(jnp.float32))
             - (loss_now.astype(jnp.float32) - loss_iso.astype(jnp.float32))),
        0.0)
    adj_n = ctx.psum(jax.ops.segment_sum(
        adj, jnp.where(slot_live, n_slot, caps.n),
        num_segments=caps.n + 1)[: caps.n])
    return gain_iso + adj_n


# ---------------------------------------------------------------------------
# 5. events-based constraint checks (Sec. VI-D, Fig. 6)
# ---------------------------------------------------------------------------
def events_validity(d: DeviceHypergraph, parts: jax.Array,
                    pins_in: jax.Array, move_to: jax.Array, seq: jax.Array,
                    gain_seq: jax.Array, caps: Caps, kcap: int,
                    params: RefineParams,
                    ctx: segops.ShardCtx = segops.ShardCtx()):
    """Returns (apply_mask[Ncap], applied_gain) — the max-cumulative-gain
    prefix of the move sequence whose end state satisfies both constraints
    for every partition (violations *inside* the prefix are permitted).

    All running counts scan in int32 (``segops.segmented_scan`` is
    dtype-preserving): the previous float32 cast was exact only while
    running sizes / distinct counts stayed below 2**24.

    Sharded mode (``ctx.axis`` set): the pins-sized inbound-event pipeline
    is fully distributed — event construction, both event *sorts*
    (``ShardCtx.sort_by``: the sample sort of ``repro.dist.sort``, stripes
    in / stripes of the sorted order out, only splitter samples gathered)
    and the segmented scans all run on each device's contiguous lane stripe
    (cross-shard scan carries via ``ShardCtx.segmented_scan``, sorted-key
    segment starts and group closings via the scalar boundary exchanges
    ``starts_from_sorted`` / ``edge_prev`` / ``edge_next``), and the
    per-seq violation deltas are psum-combined dense vectors. The
    node-sized size-event pipeline stays replicated — it is O(N), dominated
    by the O(pins) inbound pipeline."""
    mover = move_to >= 0
    ps = jnp.where(mover, parts, kcap)
    pd = jnp.where(mover, move_to, kcap)

    init_size = partition_sizes(d, parts, caps, kcap)
    init_distinct = jnp.sum(pins_in > 0, axis=1).astype(jnp.int32)  # [kcap]

    # ---- size events: (p, seq, +-size(n)) --------------------------------
    ev_p = jnp.concatenate([ps, pd])
    ev_s = jnp.concatenate([seq, seq])
    ev_d = jnp.concatenate([-d.node_size, d.node_size])
    msk = jnp.concatenate([mover, mover])
    ev_p = jnp.where(msk, ev_p, kcap)
    ev_s = jnp.where(msk, ev_s, IMAX)
    ev_d = jnp.where(msk, ev_d, 0)
    (sp, ss), (sd,) = segops.sort_by([ev_p, ev_s], [ev_d])
    starts = segops.segment_starts_from_sorted([sp])
    cum = segops.segmented_scan(sd, starts)
    size_after = init_size[jnp.clip(sp, 0, kcap - 1)] + cum
    inv = (sp < kcap) & (size_after > params.omega)
    prev_inv = jnp.where(
        starts, init_size[jnp.clip(sp, 0, kcap - 1)] > params.omega,
        jnp.concatenate([jnp.zeros((1,), bool), inv[:-1]]))
    size_vdelta = inv.astype(jnp.int32) - prev_inv.astype(jnp.int32)
    size_vseq = jnp.where(sp < kcap, ss, IMAX)

    # ---- inbound events: (p, e, seq, +-1) over e in in(n) of movers ------
    # construction on this shard's pin-lane stripe
    t, t_ok = ctx.lanes(caps.p)
    slot_live = t_ok & (t < d.n_pins)
    n_of = ctx.rows(d.node_off, t, caps.p, caps.n)
    n_safe = jnp.clip(n_of, 0, caps.n - 1)
    e_in = jnp.clip(ctx.gread(d.node_edges, t, slot_live, 0), 0, caps.e - 1)
    is_ev = (ctx.gread(d.node_is_in, t, slot_live, False)
             & slot_live & mover[n_safe])
    ie_p = jnp.concatenate([jnp.where(is_ev, ps[n_safe], kcap),
                            jnp.where(is_ev, pd[n_safe], kcap)])
    ie_e = jnp.concatenate([jnp.where(is_ev, e_in, caps.e)] * 2)
    ie_s = jnp.concatenate([jnp.where(is_ev, seq[n_safe], IMAX)] * 2)
    ie_d = jnp.concatenate([jnp.where(is_ev, -1, 0),
                            jnp.where(is_ev, 1, 0)]).astype(jnp.int32)
    # global (p, e, seq) order via the distributed sample sort: each shard
    # passes its event-lane stripe and receives its contiguous stripe of
    # the sorted order — only splitter samples are ever gathered
    # (``dist.sort``; bit-identical to the old gather-sort-stripe). Live
    # event keys are unique (seq is a permutation, pins are unique per
    # edge), so the sorted order is independent of shard interleaving.
    (ip, ie, isq), (idv,) = ctx.sort_by([ie_p, ie_e, ie_s], [ie_d],
                                        striped_in=True, striped_out=True)
    pe_start_s = ctx.starts_from_sorted([ip, ie])
    base = pins_in[jnp.clip(ip, 0, kcap - 1), jnp.clip(ie, 0, caps.e - 1)]
    cum_pe, carry_pe = ctx.segmented_scan(idv, pe_start_s)
    run = base + cum_pe
    # `run` at the element just before this stripe: its (p, e) key rides in
    # on a scalar boundary exchange, its scan value is the incoming carry
    prev_p = ctx.edge_prev(ip, ip[0])[0]
    prev_e = ctx.edge_prev(ie, ie[0])[0]
    prev_base = pins_in[jnp.clip(prev_p, 0, kcap - 1),
                        jnp.clip(prev_e, 0, caps.e - 1)]
    run_prev = jnp.concatenate([(prev_base + carry_pe)[None], run[:-1]])
    prev_run = jnp.where(pe_start_s, base, run_prev)
    live_ev = (ip < kcap) & (ie < caps.e)
    up = live_ev & (prev_run == 0) & (run > 0)     # 0 -> 1 : new distinct edge
    dn = live_ev & (prev_run > 0) & (run == 0)     # 1 -> 0 : edge left p
    dd = up.astype(jnp.int32) - dn.astype(jnp.int32)
    # distinct-count running value per (p, seq): same striped sample sort
    # over the transition deltas
    (dp2, ds2), (dd2,) = ctx.sort_by(
        [jnp.where(dd != 0, ip, kcap), jnp.where(dd != 0, isq, IMAX)], [dd],
        striped_in=True, striped_out=True)
    p_start2_s = ctx.starts_from_sorted([dp2])
    # per-(p,seq) group: state observable at the last event of the group;
    # the stripe's last element peeks at the next shard's first key (-1
    # fill: past the globally last element every group is closed)
    grp_last = ((ctx.edge_next(dp2, -1) != dp2)
                | (ctx.edge_next(ds2, -1) != ds2))
    cum2, _ = ctx.segmented_scan(dd2, p_start2_s)
    distinct_after = init_distinct[jnp.clip(dp2, 0, kcap - 1)] + cum2
    inv_i = (dp2 < kcap) & (distinct_after > params.delta)
    # forward-fill last group state within p-segment (value+1; 0 = none yet)
    state_here = jnp.where(grp_last, inv_i.astype(jnp.int32), -1)
    filled, carry_fill = ctx.segmented_scan(
        jnp.where(state_here >= 0, state_here + 1, 0),
        p_start2_s | (state_here >= 0))
    # filled at position of a group-last = its own state+1; previous group
    # state for this stripe's first element rides in on the scan carry
    prev_state = jnp.concatenate([carry_fill[None], filled[:-1]]) - 1
    nglast, _ = ctx.segmented_scan(grp_last.astype(jnp.int32), p_start2_s)
    seg_first_group = nglast <= 1
    init_inv_i = init_distinct[jnp.clip(dp2, 0, kcap - 1)] > params.delta
    prev_state = jnp.where(p_start2_s | (prev_state < 0) | seg_first_group,
                           init_inv_i.astype(jnp.int32), prev_state)
    inb_vdelta = jnp.where(grp_last & (dp2 < kcap),
                           inv_i.astype(jnp.int32) - prev_state, 0)
    inb_vseq = jnp.where(grp_last & (dp2 < kcap), ds2, IMAX)

    # ---- merge violation deltas; active count per sequence position ------
    nm_cap = caps.n  # seq positions < caps.n
    vd_size = jax.ops.segment_sum(
        size_vdelta, jnp.clip(jnp.where(size_vseq == IMAX, nm_cap, size_vseq),
                              0, nm_cap), num_segments=nm_cap + 1)[:nm_cap]
    vd_inb = ctx.psum(jax.ops.segment_sum(
        inb_vdelta, jnp.clip(jnp.where(inb_vseq == IMAX, nm_cap, inb_vseq),
                             0, nm_cap), num_segments=nm_cap + 1)[:nm_cap])
    v0 = (jnp.sum((init_size[:kcap] > params.omega).astype(jnp.int32))
          + jnp.sum((init_distinct[:kcap] > params.delta).astype(jnp.int32)))
    active = v0 + jnp.cumsum(vd_size + vd_inb)

    # ---- cumulative in-sequence gain; choose best valid prefix -----------
    n_movers = jnp.sum(mover.astype(jnp.int32))
    gain_by_seq = jnp.zeros((nm_cap,), jnp.float32).at[
        jnp.where(mover, seq, nm_cap)].add(
        jnp.where(mover, gain_seq, 0.0), mode="drop")
    cumgain = jnp.cumsum(gain_by_seq)
    pos = jnp.arange(nm_cap, dtype=jnp.int32)
    cand = (pos < n_movers) & (active == 0)
    val = jnp.where(cand, cumgain, NEG)
    t_star = jnp.argmax(val).astype(jnp.int32)
    ok = val[t_star] > 0.0
    apply_mask = mover & ok & (seq <= t_star)
    return apply_mask, jnp.where(ok, val[t_star], 0.0)


# ---------------------------------------------------------------------------
# 6. one refinement repetition + level driver
# ---------------------------------------------------------------------------
def refine_step_impl(d: DeviceHypergraph, parts: jax.Array,
                     n_parts: jax.Array, caps: Caps, kcap: int,
                     params: RefineParams, enforce_size: jax.Array,
                     ctx: segops.ShardCtx = segops.ShardCtx(),
                     tie_rank: jax.Array | None = None):
    """One full repetition (pins -> proposal -> chains -> in-seq gains ->
    events). Single source of truth for both the jitted single-device
    ``refine_step`` and ``dist.partition``'s shard_map'd racing step
    (``ctx`` shards the pins/pairs pipelines, ``tie_rank`` diversifies
    replicas)."""
    if params.use_kernels:
        from repro.kernels.pins_count import ops as pc_ops
        # replicated mesh-independent predicate (branch parity, see
        # repro.kernels): the pins kernel runs stripe-locally per shard
        fits = pc_ops.fits_kernel(d, caps)
        pins, pins_in = jax.lax.cond(
            fits,
            lambda: pc_ops.pins_matrix_kernel(d, parts, caps, kcap, ctx),
            lambda: pins_matrix(d, parts, caps, kcap, ctx))
        pins_taken = fits.astype(jnp.int32)
    else:
        pins, pins_in = pins_matrix(d, parts, caps, kcap, ctx)
        pins_taken = jnp.int32(0)
    move_to, gain_iso, _, kernel_taken = propose_moves(
        d, parts, pins, caps, kcap, params, enforce_size, n_parts, ctx)
    seq, _ = build_sequence(d, parts, move_to, gain_iso, caps, kcap, params,
                            tie_rank=tie_rank, ctx=ctx)
    gain_seq = inseq_gains(d, parts, pins, move_to, gain_iso, seq, caps,
                           kcap, ctx)
    apply_mask, applied_gain = events_validity(
        d, parts, pins_in, move_to, seq, gain_seq, caps, kcap, params, ctx)
    parts_new = jnp.where(apply_mask, jnp.where(move_to >= 0, move_to, parts),
                          parts)
    return (parts_new, applied_gain,
            jnp.sum(apply_mask.astype(jnp.int32)), kernel_taken, pins_taken)


@partial(jax.jit, static_argnames=("caps", "kcap", "params"))
def refine_step(d: DeviceHypergraph, parts: jax.Array, n_parts: jax.Array,
                caps: Caps, kcap: int, params: RefineParams,
                enforce_size):
    """One jitted repetition. ``enforce_size`` is traced, so both halves of
    a level's theta repetitions share one executable."""
    return refine_step_impl(d, parts, n_parts, caps, kcap, params,
                            jnp.asarray(enforce_size, bool))


def refine_level(d: DeviceHypergraph, parts: jax.Array, n_parts,
                 caps: Caps, kcap: int, params: RefineParams,
                 log: list | None = None):
    """Theta repetitions; first half may propose size-violating moves.
    Returns (parts, (kernel_hits, pins_hits)) — device-scalar counts of
    repetitions whose gains / pins dispatch took the Pallas branch
    (each 0..theta)."""
    n_parts = jnp.asarray(n_parts, jnp.int32)
    hits = jnp.int32(0)
    phits = jnp.int32(0)
    for rep in range(params.theta):
        enforce = rep >= params.theta // 2
        parts, g, nmv, kt, pt = refine_step(d, parts, n_parts, caps, kcap,
                                            params, enforce)
        hits = hits + kt
        phits = phits + pt
        if log is not None:
            log.append(dict(rep=rep, gain=float(g), applied=int(nmv),
                            kernel=int(kt)))
    return parts, (hits, phits)
