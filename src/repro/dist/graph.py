"""Memory-sharded hypergraph storage over the Plan mesh.

The paper's GPU design materializes the incidence structure and the
deduplicated neighborhoods in device memory to exploit set sparsity
(Sec. V-B). Our mesh port sharded the *compute* (PR 2-4) but still
replicated every O(pins) array on every device, so the largest
partitionable hypergraph shrank as the mesh grew — the opposite of what
distribution should buy. `ShardedHypergraph` fixes the storage side: the
three pins-sized arrays (`edge_pins`, `node_edges`, `node_is_in`) live as
**contiguous per-shard lane stripes over the mesh's "model" axis**
(`NamedSharding` + `jax.device_put`), padded to the stripe total
``ceil(caps.p / nshards) * nshards`` with the usual sentinels. Node/edge
sized arrays (offsets, weights, sizes, scalars) stay replicated — they are
O(N)/O(E), not the memory bottleneck — and so does everything along the
"data" axis: racing replicas *share the one sharded graph* instead of each
holding a private copy.

What stays striped vs what transiently doesn't (the memory contract):

* storage         — the three pins arrays of *every retained level* (the
                    V-cycle keeps each level's graph alive for
                    uncoarsening, so storage, not per-level temporaries,
                    dominates peak memory) hold O(pins / nshards) per
                    device.
* pipelines       — every pins/pairs-sized pipeline stage reads its own
                    lane stripe directly (`ShardCtx.gread`); the pairs
                    sized intermediates (the largest temporaries) are lane
                    stripes by construction; contraction emits the coarse
                    pins arrays as stripes (reduce-scatter packing +
                    stripe-kept incidence sort), so levels stay striped
                    end-to-end without ever materializing replicated.
* documented transients — `build_pairs` joins two *arbitrary* pin slots
                    per pair lane, the one access no lane striping can
                    serve: it rebuilds the pins column via
                    `ShardCtx.gfull` (bit-preserving psum of disjoint
                    stripes), live only inside the expansion. The dense
                    neighborhood arrays of one coarsening level
                    (`build_neighbors` output, O(nbrs)) likewise combine
                    replicated — they feed arbitrary-segment binary
                    searches — and are freed with the level step.

Exactness: striping is pure layout. `gread` returns exactly the values the
replicated array holds at this shard's lane positions, `gfull` rebuilds
bit-identical columns, and the contraction stripe outputs are the same
integers the replicated path scatters — so the `race=False` V-cycle parity
contract of `dist.partition` (bit-exact vs the single-device partitioner)
holds unchanged with sharded storage, and is regression-tested under 8
forced host devices on (2, 4) and (1, 8) meshes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hypergraph import (Caps, DeviceHypergraph, GraphDelta,
                                   HostHypergraph, apply_delta,
                                   check_fits_caps, host_from_device,
                                   packed_host_arrays)
from repro.dist.sharding import Plan

# the pins-sized storage arrays that stripe over "model"; everything else
# in DeviceHypergraph is O(N)/O(E) or scalar and stays replicated
PINS_FIELDS = ("edge_pins", "node_edges", "node_is_in")


@dataclasses.dataclass
class ShardedHypergraph:
    """A `DeviceHypergraph` whose pins-sized arrays are stripe-sharded over
    the mesh's "model" axis (and replicated over every other axis). The
    wrapper is the explicit marker the `dist.partition` drivers dispatch
    on — no shape-sniffing — and `nshards` is static pytree metadata so it
    can ride through jit untouched."""

    g: DeviceHypergraph
    nshards: int

    # ---- driver-facing passthroughs (host level loop reads these) --------
    @property
    def n_nodes(self):
        return self.g.n_nodes

    @property
    def n_edges(self):
        return self.g.n_edges

    @property
    def n_pins(self):
        return self.g.n_pins

    @property
    def edge_off(self):
        return self.g.edge_off

    @property
    def node_size(self):
        return self.g.node_size

    def pins_bytes_per_device(self) -> int:
        """Live bytes of the pins-sized storage arrays held by one device —
        the quantity that scales ~1/nshards (charted by
        benchmarks/dist_scaling.py as `graph_B`)."""
        total = 0
        for f in PINS_FIELDS:
            arr = getattr(self.g, f)
            shards = arr.addressable_shards
            total += shards[0].data.nbytes if shards else arr.nbytes
        return total


jax.tree_util.register_dataclass(ShardedHypergraph, data_fields=["g"],
                                 meta_fields=["nshards"])


def stripe_total(caps: Caps, nshards: int) -> int:
    """Padded pins-array length whose contiguous stripes tile the model
    axis: lanes are ceil-divided exactly like ``ShardCtx.lanes(caps.p)``,
    so shard i's storage stripe is shard i's compute stripe."""
    per = -(-caps.p // max(nshards, 1))
    return per * max(nshards, 1)


def model_shards(plan: Plan) -> int:
    names = tuple(plan.mesh.axis_names)
    if "model" not in names:
        raise ValueError(
            "sharded graph storage stripes over the 'model' mesh axis, but "
            f"the plan's mesh has axes {names}")
    return plan.mesh.shape["model"]


def graph_pspecs(striped: bool) -> DeviceHypergraph:
    """Per-field PartitionSpecs for a DeviceHypergraph as a shard_map
    in/out_specs pytree: pins-sized arrays stripe over "model" when
    ``striped``, everything else replicates."""
    sp = P("model") if striped else P()
    return DeviceHypergraph(
        edge_off=P(), edge_pins=sp, edge_nsrc=P(), edge_w=P(),
        node_off=P(), node_edges=sp, node_is_in=sp, node_nin=P(),
        node_size=P(), n_nodes=P(), n_edges=P(), n_pins=P())


def sharded_from_host(hg: HostHypergraph, caps: Caps,
                      plan: Plan) -> ShardedHypergraph:
    """Sharded sibling of `core.hypergraph.device_from_host`: same packed
    numpy staging arrays, but the pins-sized ones are padded to the stripe
    total and `device_put` with a "model"-striped NamedSharding (one
    host->device transfer per stripe, no replicated intermediate); all
    other arrays are placed replicated on the same mesh."""
    nshards = model_shards(plan)
    arrays = packed_host_arrays(hg, caps, pcap=stripe_total(caps, nshards))
    repl = NamedSharding(plan.mesh, P())
    striped = NamedSharding(plan.mesh, P("model"))
    placed = {
        k: jax.device_put(v, striped if k in PINS_FIELDS else repl)
        for k, v in arrays.items()
    }
    return ShardedHypergraph(g=DeviceHypergraph(**placed), nshards=nshards)


def host_from_sharded(d: ShardedHypergraph) -> HostHypergraph:
    """Host readback; fully-addressable sharded arrays assemble directly
    and `host_from_device` slices the live prefixes (stripe padding beyond
    ``caps.p`` carries sentinels past ``n_pins``, so it never surfaces)."""
    return host_from_device(d.g)


# --------------------------------------------------------------------------
# Incremental updates (streaming repartitioning)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _stripe_scatter(mesh, per: int):
    """shard_map'd sparse update of one "model"-striped pins array: each
    shard rebases the global update positions onto its own stripe and drops
    the rest (``mode="drop"``) — no cross-shard traffic at all, since every
    global lane lives on exactly one shard. Cached per (mesh, stripe size);
    jit re-specializes per update-batch shape/dtype."""

    def body(stripe, pos, val):
        i = jax.lax.axis_index("model").astype(jnp.int32)
        lo = i * per
        lp = jnp.where((pos >= lo) & (pos < lo + per), pos - lo, per)
        return stripe.at[lp].set(val, mode="drop")

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("model"), P(), P()),
                       out_specs=P("model"), check_vma=False)
    return jax.jit(fn)


def apply_delta_sharded(sh: ShardedHypergraph, hg: HostHypergraph,
                        delta: GraphDelta, caps: Caps,
                        plan: Plan) -> ShardedHypergraph:
    """Apply one ``GraphDelta`` batch to the host mirror ``hg`` (in place)
    *and* to the sharded device storage ``sh``, in place of a full
    re-upload.

    The replicated O(N)/O(E) arrays (offsets, weights, sizes, scalars)
    refresh wholesale — they are cheap and a delta shifts offsets globally
    anyway. The three O(pins) striped arrays update by **stripe-local
    scatters** of only the changed lanes: the host computes the packed-array
    diff, pads the (position, value) batch to a power of two, and each
    shard writes the updates that land in its own stripe (``mode="drop"``
    discards the rest). A striped array with no changed lanes is kept
    untouched (same device buffer); a batch touching more than half the
    lanes falls back to a fresh striped ``device_put``.

    Raises ``CapacityError`` when the post-delta graph no longer fits
    ``caps`` (the PR 5 resize trigger) **before touching device state**;
    the host mirror is still updated either way, so the caller rebuilds
    device storage from it at fresh caps."""
    nshards = model_shards(plan)
    ptot = stripe_total(caps, nshards)
    per = ptot // nshards
    old = packed_host_arrays(hg, caps, pcap=ptot)
    apply_delta(hg, delta)
    check_fits_caps(hg, caps)
    new = packed_host_arrays(hg, caps, pcap=ptot)

    repl = NamedSharding(plan.mesh, P())
    striped = NamedSharding(plan.mesh, P("model"))
    updates = {k: jax.device_put(v, repl) for k, v in new.items()
               if k not in PINS_FIELDS}
    for f in PINS_FIELDS:
        changed = np.nonzero(old[f] != new[f])[0]
        if changed.size == 0:
            continue
        if changed.size > ptot // 2:
            updates[f] = jax.device_put(new[f], striped)
            continue
        ucap = max(8, 1 << int(changed.size - 1).bit_length())
        pos = np.full((ucap,), ptot, np.int32)
        pos[: changed.size] = changed
        val = np.zeros((ucap,), new[f].dtype)
        val[: changed.size] = new[f][changed]
        fn = _stripe_scatter(plan.mesh, per)
        updates[f] = fn(getattr(sh.g, f), jnp.asarray(pos), jnp.asarray(val))
    return ShardedHypergraph(g=dataclasses.replace(sh.g, **updates),
                             nshards=nshards)
