"""Per-level V-cycle quality stats (`LevelStats`): the paper's Fig. 8 /
Table 2 per-level accounting as a first-class telemetry record.

Two halves, matched to where the drivers already have the data:

* **structural** side (nodes/edges/pins, pair/nbr expansion live counts and
  capacity occupancy, kernel-vs-segment path) comes from scalars the
  coarsening loop already syncs per level (`run_coarsen_loop` batches them
  into the one `device_get` it pays anyway for the stop/audit check) — free.
* **quality** side (connectivity/cut of the projected partition, per-block
  size and distinct-incident-hyperedge slack vs Omega/Delta) needs extra
  device reductions over each refined level's partition, so it is gated
  behind ``partition(collect_stats=True)``. `quality_scalars` dispatches a
  handful of scalar reductions per level (built on `refine.pins_matrix`,
  the same [kcap, Ecap] incidence counting the refiner itself uses) and the
  driver fetches them *once*, batched with the kernel-hit readback it
  already does after the last level — no new syncs on the hot path.
* **refinement counters** (moves proposed and applied, repetitions
  skipped and repetitions that applied no move) are summed on the device
  per level from the counts every `refine_step` already returns, and ride
  the same readback — free.

Telemetry never writes into the solve: `quality_scalars` only reads
``(d, parts)``, so collect_stats on/off is bit-identical (tested).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class LevelStats:
    """One V-cycle level, finest (level 0) first. Fields are ``None`` when
    the side that produces them did not run: coarsening fields on the
    coarsest level (it never re-enters coarsening), quality fields unless
    ``collect_stats=True`` (and on memory-sharded graphs, where the stats
    reductions would need their own shard_map plumbing)."""

    level: int
    nodes: int
    edges: int
    pins: int
    # coarsening-side (levels 0..n_levels-1)
    pairs_live: int | None = None
    nbr_entries: int | None = None
    pair_occupancy: float | None = None   # pairs_live / caps.pairs
    nbr_occupancy: float | None = None    # nbr_entries / caps.nbrs
    kernel_coarsen: int | None = None     # 0/1 Pallas path taken
    # refinement-side (every level incl. the coarsest)
    kernel_refine: int | None = None      # kernel reps (0..theta, run ones)
    moves_proposed: int | None = None     # summed over the reps that ran
    moves_applied: int | None = None      # <= moves_proposed
    skipped_reps: int | None = None       # reps after a half's first no-op
    noop_reps: int | None = None          # reps that applied no move
    connectivity: float | None = None     # of the level's refined partition
    cut_net: float | None = None
    max_size: int | None = None
    size_slack: int | None = None         # Omega - max block size
    max_inbound: int | None = None        # distinct incident h-edges
    inbound_slack: int | None = None      # Delta - max_inbound

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@functools.lru_cache(maxsize=None)
def _quality_fn(caps, kcap: int):
    """One jitted stats kernel per (caps, kcap) signature — the same cache
    discipline as the solver itself, so stats never add compile churn."""
    from repro.core.refine import partition_sizes, pins_matrix

    def f(d, parts, omega, delta):
        pins, pins_in = pins_matrix(d, parts, caps, kcap)
        e_live = jnp.arange(caps.e) < d.n_edges
        lam = jnp.where(e_live, jnp.sum((pins > 0).astype(jnp.int32),
                                        axis=0), 0)
        w = jnp.where(e_live, d.edge_w, jnp.float32(0))
        sizes = partition_sizes(d, parts, caps, kcap)
        inbound = jnp.sum((pins_in > 0).astype(jnp.int32), axis=1)
        max_size = jnp.max(sizes)
        max_inbound = jnp.max(inbound)
        return dict(
            connectivity=jnp.sum(w * jnp.maximum(lam - 1, 0)),
            cut_net=jnp.sum(w * (lam > 1)),
            max_size=max_size,
            size_slack=jnp.asarray(omega, jnp.int32) - max_size,
            max_inbound=max_inbound,
            inbound_slack=jnp.asarray(delta, jnp.int32) - max_inbound)

    return jax.jit(f)


def quality_scalars(d, parts, caps, kcap: int, omega, delta) -> dict | None:
    """Device-scalar quality stats of ``parts`` on level graph ``d`` — a
    dict of six 0-d arrays the caller batches into its existing end-of-run
    ``device_get``. Returns ``None`` for memory-sharded graph storage
    (`dist.graph.ShardedHypergraph`): its striped pins arrays can only be
    read under the shard_map the solver runs in, and stats are not worth a
    second one."""
    from repro.core.hypergraph import DeviceHypergraph

    if not isinstance(d, DeviceHypergraph):
        return None
    return _quality_fn(caps, kcap)(d, parts, jnp.int32(omega),
                                   jnp.int32(delta))


def assemble(coarsen_meta: list[dict], refine_meta: dict[int, dict]
             ) -> list[LevelStats]:
    """Zip the coarsening loop's per-level structural records with the
    refinement loop's per-level records (kernel hits, move counters and
    fetched quality scalars) into the finest-first `LevelStats` list on
    `PartitionResult.level_stats`."""
    n_levels = len(coarsen_meta)
    out = []
    for lvl in range(n_levels + 1):
        if lvl < n_levels:
            m = dict(coarsen_meta[lvl])
        else:
            m = dict(refine_meta.get(lvl, {}).get("structure") or {})
        r = refine_meta.get(lvl, {})
        q = r.get("quality") or {}
        out.append(LevelStats(
            level=lvl,
            nodes=int(m.get("nodes", 0)),
            edges=int(m.get("edges", 0)),
            pins=int(m.get("pins", 0)),
            pairs_live=m.get("pairs_live"),
            nbr_entries=m.get("nbr_entries"),
            pair_occupancy=m.get("pair_occupancy"),
            nbr_occupancy=m.get("nbr_occupancy"),
            kernel_coarsen=m.get("kernel_coarsen"),
            kernel_refine=r.get("kernel_hits"),
            moves_proposed=r.get("moves_proposed"),
            moves_applied=r.get("moves_applied"),
            skipped_reps=r.get("skipped_reps"),
            noop_reps=r.get("noop_reps"),
            connectivity=(float(q["connectivity"])
                          if "connectivity" in q else None),
            cut_net=float(q["cut_net"]) if "cut_net" in q else None,
            max_size=int(q["max_size"]) if "max_size" in q else None,
            size_slack=int(q["size_slack"]) if "size_slack" in q else None,
            max_inbound=(int(q["max_inbound"])
                         if "max_inbound" in q else None),
            inbound_slack=(int(q["inbound_slack"])
                           if "inbound_slack" in q else None)))
    return out
