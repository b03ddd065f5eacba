"""Hypergraph containers: host (numpy, ragged) and device (JAX, static-capacity).

The paper stores hypergraphs as two-level compressed sparse structures
(Fig. 2): a segmented data array plus an offsets array, with h-edge pins
stored *sources first* and node incidence stored *inbound h-edges first*,
each with a secondary count array (``|src(e)|`` / ``|in(n)|``).

We keep exactly that layout. The TPU adaptation is that device arrays are
**capacity-padded with validity counts** (XLA needs static shapes): the
coarsened level-(l+1) hypergraph lives in arrays of the same capacity as
level l, with ``n_nodes/n_edges/n_pins`` giving the live prefix sizes.
Padding lanes carry sentinels that sort to the end — the static-shape
analogue of the paper's idle CUDA lanes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NSENT = np.int32(2**31 - 1)  # sentinel id for padding lanes


class CapacityError(ValueError):
    """A static device capacity was exceeded by the live data.

    The capacity-padded device pipelines clip/drop out-of-capacity lanes
    (XLA needs static shapes), so an undersized ``Caps`` would otherwise
    corrupt results *silently* — e.g. a truncated neighborhood CSR simply
    drops candidate pairs. The drivers therefore audit the live counts
    host-side every level (see ``check_expansion_caps``) and raise this
    instead of mis-partitioning."""


def check_expansion_caps(caps: "Caps", n_pairs_live, n_nbr_entries=None):
    """Host-side overflow audit for one level's pair/neighborhood expansion.

    ``n_pairs_live`` is the *true* ordered-pin-pair count (``build_pairs``
    derives it from ``edge_off`` alone, so it is exact even when the lane
    expansion was truncated); ``n_nbr_entries`` the deduplicated
    neighborhood entry count from ``build_neighbors`` (exact only while the
    pair expansion itself fit — hence pairs are checked first). Either may
    be a device scalar; syncing them here is the per-level host round-trip
    the drivers already pay for the ``n_pairs`` stop check."""
    pl = int(n_pairs_live)
    if pl > caps.pairs:
        raise CapacityError(
            f"pair-expansion overflow: {pl} live ordered pin pairs exceed "
            f"Caps.pairs={caps.pairs}; lanes past capacity were dropped. "
            f"Raise pair_cap (Caps.for_host computes the exact bound by "
            f"default).")
    if n_nbr_entries is not None:
        nl = int(n_nbr_entries)
        if nl > caps.nbrs:
            raise CapacityError(
                f"neighborhood overflow: {nl} deduplicated (node, neighbor) "
                f"entries exceed Caps.nbrs={caps.nbrs}; the compacted CSR "
                f"would have been truncated. Raise nbr_cap.")


# --------------------------------------------------------------------------
# Host container
# --------------------------------------------------------------------------
@dataclasses.dataclass
class HostHypergraph:
    """Ragged numpy hypergraph; ground-truth structure for IO / oracles.

    ``drift_pins`` accumulates the number of pins touched by ``apply_delta``
    batches since the last full (cold) solve — the numerator of the
    ``drift`` metric that ``core.partitioner.repartition`` compares against
    its fallback threshold. A cold solve calls ``reset_drift()``.
    """

    n_nodes: int
    edge_off: np.ndarray    # [E+1] int64
    edge_pins: np.ndarray   # [P]   int32 — sources first within each edge
    edge_nsrc: np.ndarray   # [E]   int32
    edge_w: np.ndarray      # [E]   float32
    drift_pins: int = 0     # pins touched by deltas since last full solve

    def __post_init__(self):
        self.edge_off = np.asarray(self.edge_off, np.int64)
        self.edge_pins = np.asarray(self.edge_pins, np.int32)
        self.edge_nsrc = np.asarray(self.edge_nsrc, np.int32)
        self.edge_w = np.asarray(self.edge_w, np.float32)

    @property
    def n_edges(self) -> int:
        return len(self.edge_w)

    @property
    def n_pins(self) -> int:
        return int(self.edge_off[-1])

    @property
    def drift(self) -> float:
        """Fraction of the current pin population touched by deltas since
        the last full solve, clamped to 1.0. The streaming repartitioner
        falls back to a cold V-cycle once this crosses its threshold."""
        return min(1.0, self.drift_pins / max(self.n_pins, 1))

    def reset_drift(self) -> None:
        self.drift_pins = 0

    def edge(self, e: int) -> np.ndarray:
        return self.edge_pins[self.edge_off[e]: self.edge_off[e + 1]]

    def src(self, e: int) -> np.ndarray:
        return self.edge_pins[self.edge_off[e]: self.edge_off[e] + self.edge_nsrc[e]]

    def dst(self, e: int) -> np.ndarray:
        return self.edge_pins[self.edge_off[e] + self.edge_nsrc[e]: self.edge_off[e + 1]]

    def validate(self) -> None:
        assert self.edge_off[0] == 0 and np.all(np.diff(self.edge_off) >= 0)
        assert self.edge_pins.min(initial=0) >= 0
        assert self.edge_pins.max(initial=-1) < self.n_nodes
        for e in range(self.n_edges):
            pins = self.edge(e)
            assert len(np.unique(pins)) == len(pins), f"duplicate pin in edge {e}"
            assert 0 <= self.edge_nsrc[e] <= len(pins)

    # -- derived structure (numpy reference for incidence construction) ----
    def incidence(self):
        """Returns (node_off[N+1], node_edges[P], node_is_in[P], node_nin[N])
        with inbound edges first per node, ordered by edge id within group."""
        E, P, N = self.n_edges, self.n_pins, self.n_nodes
        pin_edge = np.repeat(np.arange(E, dtype=np.int32),
                             np.diff(self.edge_off).astype(np.int64))
        rel = np.arange(P, dtype=np.int64) - self.edge_off[pin_edge]
        is_dst = rel >= self.edge_nsrc[pin_edge]
        order = np.lexsort((pin_edge, ~is_dst, self.edge_pins))
        node_edges = pin_edge[order]
        node_is_in = is_dst[order]
        counts = np.bincount(self.edge_pins, minlength=N)
        node_off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        node_nin = np.bincount(self.edge_pins[is_dst], minlength=N).astype(np.int32)
        return node_off, node_edges, node_is_in, node_nin

    def stats(self) -> dict:
        card = np.diff(self.edge_off)
        node_off, *_ = self.incidence()
        deg = np.diff(node_off)
        return dict(
            n_nodes=self.n_nodes, n_edges=self.n_edges, n_pins=self.n_pins,
            max_card=int(card.max(initial=0)), avg_card=float(card.mean()) if len(card) else 0.0,
            max_deg=int(deg.max(initial=0)), avg_deg=float(deg.mean()) if len(deg) else 0.0,
            pair_expansion=int((card.astype(np.int64) ** 2 - card).sum()),
        )


# --------------------------------------------------------------------------
# Incremental updates (streaming repartitioning)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """One batched structural update against a ``HostHypergraph``.

    **Id semantics.** Every node/edge id in a delta refers to the graph
    *before* the batch is applied. New node ids are knowable upfront
    (``old_n .. old_n + add_nodes - 1``) and may appear in ``add_pins`` /
    ``add_edges`` of the same batch. Edge ids shift down after deletions
    (edge order is otherwise preserved, then ``add_edges`` append), so a
    *subsequent* delta must use post-batch ids.

    **Node deletion is a tombstone**: every pin of the node is dropped from
    every edge, but the id stays allocated as an isolated node — node ids
    are stable, so a previous partition vector remains aligned (the warm
    path's core invariant).

    Fields:
      * ``add_nodes`` — number of fresh (isolated) nodes to append.
      * ``del_nodes`` — node ids to tombstone.
      * ``del_edges`` — edge ids to remove outright.
      * ``add_edges`` — ``(pins, nsrc, w)`` triples; pins sources-first.
      * ``add_pins`` — ``(edge, node)`` pairs appended as *dst* pins.
      * ``del_pins`` — ``(edge, node)`` pairs removed (nsrc adjusts if the
        removed pin was a source).
    """

    add_nodes: int = 0
    del_nodes: tuple = ()
    del_edges: tuple = ()
    add_edges: tuple = ()   # of (pins: array-like, nsrc: int, w: float)
    add_pins: tuple = ()    # of (edge, node)
    del_pins: tuple = ()    # of (edge, node)


def apply_delta(hg: HostHypergraph, delta: GraphDelta) -> int:
    """Apply one delta batch to ``hg`` **in place**; returns the number of
    pins touched (also accumulated onto ``hg.drift_pins``).

    Application order: pin deletions -> node tombstones -> pin insertions ->
    edge deletions -> edge insertions -> node-space growth. Touched pins =
    every explicitly edited pin + every pin of a deleted or inserted edge +
    every pin dropped by a tombstone. Raises ``ValueError`` on ids that do
    not resolve against the pre-batch graph (a malformed delta must never
    half-apply silently — callers treat the graph as corrupt if this
    escapes mid-batch, exactly like a failed transaction)."""
    new_n = hg.n_nodes + int(delta.add_nodes)
    pins = [list(map(int, hg.edge(e))) for e in range(hg.n_edges)]
    nsrc = [int(v) for v in hg.edge_nsrc]
    wts = [float(v) for v in hg.edge_w]
    E = len(pins)
    touched = 0

    for e, v in delta.del_pins:
        e, v = int(e), int(v)
        if not 0 <= e < E:
            raise ValueError(f"del_pins: edge {e} out of range")
        try:
            i = pins[e].index(v)
        except ValueError:
            raise ValueError(f"del_pins: node {v} is not a pin of edge {e}")
        del pins[e][i]
        if i < nsrc[e]:
            nsrc[e] -= 1
        touched += 1

    dead_nodes = {int(v) for v in delta.del_nodes}
    if dead_nodes:
        for v in dead_nodes:
            if not 0 <= v < hg.n_nodes:
                raise ValueError(f"del_nodes: node {v} out of range")
        for e in range(E):
            lst = pins[e]
            hit = [i for i, v in enumerate(lst) if v in dead_nodes]
            if hit:
                nsrc[e] -= sum(1 for i in hit if i < nsrc[e])
                pins[e] = [v for i, v in enumerate(lst) if v not in dead_nodes]
                touched += len(hit)

    for e, v in delta.add_pins:
        e, v = int(e), int(v)
        if not 0 <= e < E:
            raise ValueError(f"add_pins: edge {e} out of range")
        if not 0 <= v < new_n:
            raise ValueError(f"add_pins: node {v} out of range")
        if v in pins[e]:
            raise ValueError(f"add_pins: node {v} already a pin of edge {e}")
        pins[e].append(v)
        touched += 1

    dead_edges = {int(e) for e in delta.del_edges}
    for e in dead_edges:
        if not 0 <= e < E:
            raise ValueError(f"del_edges: edge {e} out of range")
        touched += len(pins[e])
    keep = [e for e in range(E) if e not in dead_edges]
    pins = [pins[e] for e in keep]
    nsrc = [nsrc[e] for e in keep]
    wts = [wts[e] for e in keep]

    for epins, ensrc, ew in delta.add_edges:
        epins = [int(v) for v in np.asarray(epins).ravel()]
        if len(set(epins)) != len(epins):
            raise ValueError("add_edges: duplicate pin within an edge")
        for v in epins:
            if not 0 <= v < new_n:
                raise ValueError(f"add_edges: node {v} out of range")
        if not 0 <= int(ensrc) <= len(epins):
            raise ValueError("add_edges: nsrc out of range")
        pins.append(epins)
        nsrc.append(int(ensrc))
        wts.append(float(ew))
        touched += len(epins)

    lens = np.array([len(p) for p in pins], np.int64)
    hg.n_nodes = new_n
    hg.edge_off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    hg.edge_pins = (np.concatenate([np.asarray(p, np.int32) for p in pins])
                    if pins and sum(lens) else np.zeros(0, np.int32))
    hg.edge_nsrc = np.asarray(nsrc, np.int32)
    hg.edge_w = np.asarray(wts, np.float32)
    hg.drift_pins += touched
    return touched


def check_fits_caps(hg: HostHypergraph, caps: "Caps") -> None:
    """Resize trigger for delta-updated graphs: raises ``CapacityError``
    when ``hg`` no longer fits a previously computed ``Caps`` — live counts
    against the node/edge/pin capacities, plus the PR 5 pair-expansion audit
    (``check_expansion_caps``), since inserted edges can grow the pair total
    past ``caps.pairs``. The kernel tile bounds (``d_max``/``h0``) are *not*
    checked here: the Pallas dispatches guard them with their own runtime
    ``fits_kernel`` predicates and fall back to the segment paths, so stale
    tile bounds degrade speed, never correctness."""
    if hg.n_nodes > caps.n or hg.n_edges > caps.e or hg.n_pins > caps.p:
        raise CapacityError(
            f"delta-updated graph outgrew its capacities: "
            f"nodes {hg.n_nodes}/{caps.n}, edges {hg.n_edges}/{caps.e}, "
            f"pins {hg.n_pins}/{caps.p}. Rebuild device storage at fresh "
            f"Caps (Caps.for_host) — the warm solver does this "
            f"automatically.")
    check_expansion_caps(caps, host_pair_count(hg))


# --------------------------------------------------------------------------
# Static capacities
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Caps:
    """Static device capacities. Monotone under coarsening (coarse pins
    dedup, so pins/pairs/neighbor totals never grow level-over-level), hence
    one jit signature serves the entire multi-level run."""

    n: int      # node capacity
    e: int      # edge capacity
    p: int      # pins capacity
    pairs: int  # ordered-pin-pair expansion capacity (sum_e |e|^2 - |e|)
    nbrs: int   # unique (node, neighbor) capacity
    d_max: int = 0  # max h-edge cardinality (monotone non-increasing
                    # under coarsening: coarse pins only deduplicate)
    h0: int = 0   # level-0 max node incidence degree (kernel tile bound)
    l0: int = 0   # level-0 max per-node traversal sum_{e in I(n)} (|e|-1)
    u0: int = 0   # level-0 bound on unique neighbors per node

    @staticmethod
    def for_host(hg: HostHypergraph, pair_cap: int | None = None,
                 nbr_cap: int | None = None) -> "Caps":
        st = hg.stats()
        pairs = int(st["pair_expansion"]) if pair_cap is None else pair_cap
        nbrs = min(pairs, hg.n_nodes * max(1, hg.n_nodes - 1)) if nbr_cap is None else nbr_cap
        nbrs = max(nbrs, 1)
        # per-node traversal bound for the pair_scores kernel tiles
        node_off, node_edges, _, _ = hg.incidence()
        card = np.diff(hg.edge_off).astype(np.int64)
        trav = np.maximum(card[node_edges] - 1, 0)
        if hg.n_pins:
            # clip: trailing isolated nodes put their offset at P itself,
            # which reduceat rejects; where() zeroes those segments anyway
            idx = node_off[:-1].astype(np.int64).clip(0, len(trav) - 1)
            trav_per_node = np.add.reduceat(trav, idx)
        else:
            trav_per_node = np.zeros(1)
        trav_per_node = np.where(np.diff(node_off) > 0, trav_per_node, 0)
        l0 = int(trav_per_node.max(initial=0))
        return Caps(n=max(hg.n_nodes, 1), e=max(hg.n_edges, 1),
                    p=max(hg.n_pins, 1), pairs=max(pairs, 1), nbrs=nbrs,
                    d_max=int(st["max_card"]), h0=int(st["max_deg"]),
                    l0=max(l0, 1), u0=max(min(l0, hg.n_nodes - 1), 1))


# --------------------------------------------------------------------------
# Device container
# --------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeviceHypergraph:
    """Capacity-padded device hypergraph (all int32/float32)."""

    edge_off: jax.Array    # [Ecap+1]
    edge_pins: jax.Array   # [Pcap]  — NSENT beyond n_pins
    edge_nsrc: jax.Array   # [Ecap]
    edge_w: jax.Array      # [Ecap] f32
    node_off: jax.Array    # [Ncap+1]
    node_edges: jax.Array  # [Pcap] — NSENT beyond n_pins
    node_is_in: jax.Array  # [Pcap] bool
    node_nin: jax.Array    # [Ncap]
    node_size: jax.Array   # [Ncap] int32 cluster sizes (0 beyond n_nodes)
    n_nodes: jax.Array     # scalar int32
    n_edges: jax.Array
    n_pins: jax.Array

    @property
    def ncap(self) -> int:
        return self.node_off.shape[0] - 1

    @property
    def ecap(self) -> int:
        return self.edge_off.shape[0] - 1

    @property
    def pcap(self) -> int:
        return self.edge_pins.shape[0]


def packed_host_arrays(hg: HostHypergraph, caps: Caps,
                       pcap: int | None = None) -> dict:
    """Capacity-padded numpy staging arrays for a device hypergraph.

    ``pcap`` overrides the padded length of the three pins-sized arrays
    (``edge_pins``/``node_edges``/``node_is_in``) — ``dist.graph`` pads them
    to the shard-stripe total (``ceil(caps.p / nshards) * nshards``) so the
    stripes tile the mesh's model axis; the extra lanes carry the same
    sentinels as ordinary capacity padding."""
    node_off, node_edges, node_is_in, node_nin = hg.incidence()
    N, E, P = hg.n_nodes, hg.n_edges, hg.n_pins
    pcap = caps.p if pcap is None else pcap

    def pad(a, cap, fill, dtype):
        out = np.full((cap,), fill, dtype=dtype)
        out[: len(a)] = a
        return out

    eo = np.full((caps.e + 1,), P, np.int32)
    eo[: E + 1] = hg.edge_off
    no = np.full((caps.n + 1,), P, np.int32)
    no[: N + 1] = node_off
    return dict(
        edge_off=eo,
        edge_pins=pad(hg.edge_pins, pcap, NSENT, np.int32),
        edge_nsrc=pad(hg.edge_nsrc, caps.e, 0, np.int32),
        edge_w=pad(hg.edge_w, caps.e, 0.0, np.float32),
        node_off=no,
        node_edges=pad(node_edges, pcap, NSENT, np.int32),
        node_is_in=pad(node_is_in, pcap, False, bool),
        node_nin=pad(node_nin, caps.n, 0, np.int32),
        node_size=pad(np.ones(N, np.int32), caps.n, 0, np.int32),
        n_nodes=np.int32(N),
        n_edges=np.int32(E),
        n_pins=np.int32(P),
    )


def device_from_host(hg: HostHypergraph, caps: Caps) -> DeviceHypergraph:
    arrays = packed_host_arrays(hg, caps)
    return DeviceHypergraph(**{k: jnp.asarray(v) for k, v in arrays.items()})


def host_from_device(d: DeviceHypergraph) -> HostHypergraph:
    n_nodes = int(d.n_nodes)
    n_edges = int(d.n_edges)
    n_pins = int(d.n_pins)
    return HostHypergraph(
        n_nodes=n_nodes,
        edge_off=np.asarray(d.edge_off)[: n_edges + 1],
        edge_pins=np.asarray(d.edge_pins)[:n_pins],
        edge_nsrc=np.asarray(d.edge_nsrc)[:n_edges],
        edge_w=np.asarray(d.edge_w)[:n_edges],
    )


# --------------------------------------------------------------------------
# In-jit derived structures
# --------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PairExpansion:
    """Flat ordered-pin-pair traversal: one entry per (edge, pin i, pin j!=i).

    This is the linearization of the paper's nested traversal
    ``forall n, forall e in I(n), forall m in e`` (Eq. 4): entry k visits
    node n = pins[i] seeing neighbor m = pins[j] through edge e. ``slot_n``
    is the global pin-slot of (e, i) — a unique id for the incidence pair
    (n, e), used as the segment key for per-(n,e) reductions.
    """

    edge: jax.Array      # [L] int32 edge id (NSENT padding)
    n: jax.Array         # [L] int32 visiting node
    m: jax.Array         # [L] int32 seen neighbor
    w_norm: jax.Array    # [L] f32 omega(e)/|e|
    w: jax.Array         # [L] f32 omega(e)
    both_dst: jax.Array  # [L] bool  n,m in dst(e)  (inter() contribution)
    slot_n: jax.Array    # [L] int32 pin-slot of n in e  == (n,e) segment id
    valid: jax.Array     # [L] bool
    n_pairs: jax.Array   # scalar int32


def build_pairs(d: DeviceHypergraph, caps: Caps,
                idx: jax.Array | None = None,
                idx_ok: jax.Array | None = None,
                ctx=None) -> PairExpansion:
    """``idx``/``idx_ok`` (from ``ShardCtx.lanes(caps.pairs)``) restrict the
    expansion to one shard's contiguous lane stripe; default is all lanes.

    ``ctx`` (a ``segops.ShardCtx``) matters only for memory-sharded graph
    storage (``ctx.graph_striped``): the expansion joins two *arbitrary*
    pin slots per pair lane (``edge_pins[slot_n]`` / ``edge_pins[slot_m]``)
    — the one access pattern lane striping cannot serve — so the pins
    column is transiently rebuilt full-length via ``ctx.gfull`` for the
    duration of the expansion (O(pins) live, freed after; the persistent
    storage stays striped — see ``dist.graph``)."""
    from repro.utils import segops

    if ctx is None:
        ctx = segops.ShardCtx()
    edge_pins = ctx.gfull(d.edge_pins)
    L = caps.pairs
    ecap = d.ecap
    card = (d.edge_off[1:] - d.edge_off[:-1]).astype(jnp.int32)  # [Ecap]
    live_edge = jnp.arange(ecap, dtype=jnp.int32) < d.n_edges
    card = jnp.where(live_edge, card, 0)
    pcnt = card * jnp.maximum(card - 1, 0)
    poff = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(pcnt)])
    n_pairs = poff[-1]

    if idx is None:
        idx = jnp.arange(L, dtype=jnp.int32)
    e = jnp.clip(jnp.searchsorted(poff, idx, side="right").astype(jnp.int32) - 1,
                 0, ecap - 1)
    valid = idx < n_pairs
    if idx_ok is not None:
        valid = valid & idx_ok
    r = idx - poff[e]
    c = jnp.maximum(card[e], 2)
    i = r // (c - 1)
    j0 = r % (c - 1)
    j = j0 + (j0 >= i)
    base = d.edge_off[e]
    slot_n = base + i
    slot_m = base + j
    safe = lambda s: jnp.clip(s, 0, caps.p - 1)
    n = jnp.where(valid, edge_pins[safe(slot_n)], NSENT)
    m = jnp.where(valid, edge_pins[safe(slot_m)], NSENT)
    nsrc = d.edge_nsrc[e]
    both_dst = valid & (i >= nsrc) & (j >= nsrc)
    wn = jnp.where(valid, d.edge_w[e] / jnp.maximum(card[e], 1), 0.0)
    w = jnp.where(valid, d.edge_w[e], 0.0)
    return PairExpansion(
        edge=jnp.where(valid, e, NSENT), n=n, m=m, w_norm=wn, w=w,
        both_dst=both_dst, slot_n=jnp.where(valid, slot_n, caps.p),
        valid=valid, n_pairs=n_pairs)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Neighborhoods:
    """Materialized deduplicated neighborhoods (paper Sec. V-B), CSR by node,
    ids ascending within each node's segment (binary-searchable)."""

    off: jax.Array       # [Ncap+1] int32
    ids: jax.Array       # [NBcap] int32 neighbor ids (NSENT padding)
    n_entries: jax.Array  # scalar int32


def build_neighbors(pairs: PairExpansion, d: DeviceHypergraph, caps: Caps,
                    ctx=None) -> Neighborhoods:
    """Sort-dedup the pair expansion into unique (n, m) adjacency.

    TPU adaptation of the paper's one-time hash-set construction: a stable
    two-key sort + boundary flags + compaction gives the same deduplicated
    CSR with deterministic ordering.

    ``ctx`` (a ``segops.ShardCtx``): ``pairs`` is then one shard's lane
    stripe; the (n, m) keys go through the distributed sample sort
    (``ctx.sort_by``, stripes in / stripes out — only splitter samples are
    gathered), dedup flags come from stripe-boundary-aware start flags, the
    compaction positions from a cross-shard cumsum carry, and the dense
    neighborhood arrays combine by psum of disjoint scatters. Bit-identical
    to the single-device build, which remains the ``ctx=None`` degenerate
    case of the same code path.
    """
    from repro.utils import segops

    if ctx is None:
        ctx = segops.ShardCtx()
    keyn = jnp.where(pairs.valid, pairs.n, NSENT)
    keym = jnp.where(pairs.valid, pairs.m, NSENT)
    (skn, skm), _ = ctx.sort_by([keyn, keym], [], striped_in=True,
                                striped_out=True)
    starts = ctx.starts_from_sorted([skn, skm])
    keep = starts & (skn != NSENT)
    f = keep.astype(jnp.int32)
    pos = ctx.cumsum(f) - f                      # global compaction slots
    n_entries = ctx.psum(jnp.sum(f))
    slot = jnp.where(keep, jnp.minimum(pos, caps.nbrs), caps.nbrs)
    live = jnp.arange(caps.nbrs, dtype=jnp.int32) < n_entries
    ids = ctx.psum(jnp.zeros((caps.nbrs + 1,), jnp.int32)
                   .at[slot].set(skm, mode="drop")[: caps.nbrs])
    ids = jnp.where(live, ids, NSENT)
    owner = ctx.psum(jnp.zeros((caps.nbrs + 1,), jnp.int32)
                     .at[slot].set(skn, mode="drop")[: caps.nbrs])
    owner = jnp.where(live, owner, NSENT)
    counts = jax.ops.segment_sum(
        jnp.ones_like(owner), jnp.where(owner == NSENT, caps.n, owner),
        num_segments=caps.n + 1)[: caps.n]
    off = segops.offsets_from_counts(counts.astype(jnp.int32))
    return Neighborhoods(off=off, ids=ids, n_entries=n_entries)


def host_pair_count(hg: HostHypergraph) -> int:
    """Exact (int64) ordered-pin-pair expansion size on host. The drivers
    audit this against ``Caps.pairs`` *before* any device work: pair totals
    are monotone non-increasing under coarsening (coarse pins dedup — see
    ``Caps``), so once level 0 fits, every coarser level's count is bounded
    by ``caps.pairs < 2**31`` and the per-level int32 device counts
    (``device_pair_count``, ``build_pairs``'s cumsum) are exact — no wrap
    can slip an overflow past the audit."""
    card = np.diff(hg.edge_off).astype(np.int64)
    return int((card * np.maximum(card - 1, 0)).sum())


@jax.jit
def device_pair_count(edge_off: jax.Array) -> jax.Array:
    """Live ordered-pin-pair expansion size ``sum_e |e|^2 - |e|`` computed
    on device from the (capacity-padded) offsets — dead edges beyond
    ``n_edges`` have zero cardinality by the padding convention, so no live
    mask is needed. int32, exact only while the total stays below 2**31 —
    guaranteed by the drivers' upfront ``host_pair_count`` audit plus pair
    monotonicity under coarsening (this is a per-level defense-in-depth
    recheck, not the primary overflow guard)."""
    card = (edge_off[1:] - edge_off[:-1]).astype(jnp.int32)
    return jnp.sum(card * jnp.maximum(card - 1, 0))


def shrink_device(d: DeviceHypergraph, caps: Caps) -> tuple[DeviceHypergraph, Caps]:
    """Re-bucket capacities to the next power of two above the live sizes
    between coarsening levels.

    Baseline keeps level-0 capacities for every level (one jit signature,
    but each level pays O(caps) work on mostly-dead lanes). Bucketing trades
    a handful of extra compilations (one per pow2 bucket, amortized across
    levels) for geometric work decay. Edge capacity never shrinks (edge ids
    persist across levels, paper Sec. V-E).

    The live pair count is reduced on device (``device_pair_count``) and
    read back in the same ``device_get`` as the node/pin scalars — one
    host sync of three scalars per bucketed level, replacing the previous
    blocking O(E) ``edge_off`` readback.
    """
    import math as _math
    from repro.obs import trace as otrace
    n_live, p_live, pair_live = (int(v) for v in otrace.device_sync(
        [d.n_nodes, d.n_pins, device_pair_count(d.edge_off)],
        "shrink_scalars"))
    new_n = 1 << max(0, _math.ceil(_math.log2(max(n_live, 1))))
    new_p = 1 << max(0, _math.ceil(_math.log2(max(p_live, 1))))
    if new_n >= caps.n and new_p >= caps.p:
        return d, caps
    new_n = min(new_n, caps.n)
    new_p = min(new_p, caps.p)
    new_pairs = min(caps.pairs,
                    1 << max(0, _math.ceil(_math.log2(max(pair_live, 1)))))
    new_nbrs = min(caps.nbrs, new_pairs)
    caps2 = Caps(n=new_n, e=caps.e, p=new_p, pairs=max(new_pairs, 1),
                 nbrs=max(new_nbrs, 1), d_max=caps.d_max, h0=caps.h0,
                 l0=caps.l0, u0=caps.u0)
    d2 = DeviceHypergraph(
        edge_off=d.edge_off,
        edge_pins=d.edge_pins[:new_p],
        edge_nsrc=d.edge_nsrc,
        edge_w=d.edge_w,
        node_off=d.node_off[: new_n + 1],
        node_edges=d.node_edges[:new_p],
        node_is_in=d.node_is_in[:new_p],
        node_nin=d.node_nin[:new_n],
        node_size=d.node_size[:new_n],
        n_nodes=d.n_nodes, n_edges=d.n_edges, n_pins=d.n_pins,
    )
    return d2, caps2
