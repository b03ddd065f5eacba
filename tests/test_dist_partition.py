"""Mesh-sharded V-cycle (`dist.partition`) vs the single-device
partitioner.

Parity contract: with racing off every replica runs the identity tie-break
permutation, and every sharded reduction is either an integer psum, a
lexicographic (value, id) pmax, or a stripe-ordered gather + replicated
float reduction (see dist/partition.py), so the *full* distributed V-cycle
— sharded coarsening + contraction + sharded refinement — must reproduce
the single-device `partition` *bit-for-bit* (same parts array, same audit,
same level count). Memory-sharded graph storage (`shard_graph=True`,
`dist.graph.ShardedHypergraph`: pins-sized arrays as per-shard stripes
over "model") is pure layout, so the same bit-for-bit contract covers it
on both (2, 4) and (1, 8) meshes. The 8-forced-host-device variants run in a subprocess so
the main test session keeps its single-device view; CI's slow job
additionally runs this file with XLA_FLAGS already forcing 8 devices (see
.github/workflows/ci.yml), which the in-process tests pick up."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

_GRAPH = dict(n_layers=4, width=24, fanout=6, window=8, seed=3)
_CONSTRAINTS = dict(omega=16, delta=64, theta=4)


def _parity_check():
    """Shared body: single-device partition vs dist partition on whatever
    mesh the current process supports. Returns (r_single, r_dist_norace,
    r_dist_race, r_dist_norace_sharded_storage)."""
    import jax
    from repro.core import generate
    from repro.core.partitioner import partition
    from repro.dist.sharding import Plan

    n = len(jax.devices())
    replicas = 2 if n >= 2 else 1
    mesh = jax.make_mesh((replicas, n // replicas), ("data", "model"))
    plan = Plan.make(mesh)
    hg = generate.snn_layered(**_GRAPH)
    r0 = partition(hg, **_CONSTRAINTS)
    r1 = partition(hg, **_CONSTRAINTS, plan=plan, race=False)
    r2 = partition(hg, **_CONSTRAINTS, plan=plan, race=True)
    r3 = partition(hg, **_CONSTRAINTS, plan=plan, race=False,
                   shard_graph=True)
    return r0, r1, r2, r3


def test_dist_partition_parity_single_device():
    """On a 1-device mesh the raced+sharded driver degenerates to exactly
    the single-device pipeline (fast, runs everywhere)."""
    import jax
    r0, r1, r2, r3 = _parity_check()
    assert np.array_equal(r0.parts, r1.parts)
    assert r0.audit["connectivity"] == r1.audit["connectivity"]
    assert r0.n_levels == r1.n_levels  # coarsening rode the mesh too
    # memory-sharded storage is pure layout: bit-exact in any mesh shape
    assert np.array_equal(r0.parts, r3.parts)
    assert r0.audit == r3.audit
    assert r0.n_levels == r3.n_levels
    if len(jax.devices()) == 1:
        # one replica -> replica 0 -> identity permutation even when racing
        assert np.array_equal(r0.parts, r2.parts)
    else:
        assert r2.audit["size_ok"] and r2.audit["inbound_ok"]


def test_coarsen_contract_level_parity():
    """`dist.partition.coarsen_level`/`contract_level` vs the single-device
    `coarsen_step`/`contract`, bit-exact field by field — on however many
    devices this session sees (8 in CI's forced-fan-out step)."""
    import dataclasses

    import jax
    from repro.core import generate
    from repro.core import hypergraph as H
    from repro.core.coarsen import CoarsenParams, coarsen_step
    from repro.core.contract import contract
    from repro.dist.sharding import Plan
    import repro.dist.partition as dp

    n = len(jax.devices())
    plan = Plan.make(jax.make_mesh((1, n), ("data", "model")))
    hg = generate.snn_layered(**_GRAPH)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    cp = CoarsenParams(omega=_CONSTRAINTS["omega"],
                       delta=_CONSTRAINTS["delta"])
    m0, np0, props0 = coarsen_step(d, caps, cp)
    m1, np1, ovf1 = dp.coarsen_level(d, caps, cp, plan)
    assert np.array_equal(np.asarray(m0), np.asarray(m1))
    assert int(np0) == int(np1)
    # overflow diagnostics agree with the single-device step and with caps
    assert int(props0.n_pairs_live) == int(ovf1[0]) <= caps.pairs
    assert int(props0.n_nbr_entries) == int(ovf1[1]) <= caps.nbrs
    # so do the slots refused by Omega and by Delta, summed over stripes
    assert (int(props0.n_refused_omega), int(props0.n_refused_delta)) == \
        tuple(int(v) for v in ovf1[3:])
    d20, g0 = contract(d, m0, caps)
    d21, g1 = dp.contract_level(d, m1, caps, plan)
    assert np.array_equal(np.asarray(g0), np.asarray(g1))
    for f in dataclasses.fields(d20):
        np.testing.assert_array_equal(
            np.asarray(getattr(d20, f.name)),
            np.asarray(getattr(d21, f.name)), err_msg=f.name)


@pytest.mark.slow
def test_dist_partition_parity_inprocess_8dev():
    """Runs only when the session itself was launched with 8 forced host
    devices (the CI slow job's dedicated step)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    r0, r1, r2, r3 = _parity_check()
    assert np.array_equal(r0.parts, r1.parts)
    assert r0.audit == r1.audit
    assert r2.audit["size_ok"] and r2.audit["inbound_ok"]
    # memory-sharded graph storage (pins stripes over "model", shared by
    # the racing replicas): bit-exact with the single-device run
    assert np.array_equal(r0.parts, r3.parts)
    assert r0.audit == r3.audit
    assert r0.n_levels == r3.n_levels


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import generate
    from repro.core.partitioner import partition
    from repro.dist.sharding import Plan
    from repro.utils import segops

    assert len(jax.devices()) == 8

    # --- cross-shard segmented-scan carries on a real 8-way mesh ---------
    mesh1 = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(-5, 6, size=64).astype(np.int32))
    starts = rng.random(64) < 0.25
    starts[0] = True
    starts = jnp.asarray(starts)
    ctx = segops.ShardCtx(axis="model", nshards=8)
    def body(v, s):
        out, _ = ctx.segmented_scan(ctx.stripe(v), ctx.stripe(s))
        return ctx.gather(out)
    f = jax.shard_map(body, mesh=mesh1, in_specs=(P(), P()),
                      out_specs=P(), check_vma=False)
    got = np.asarray(jax.jit(f)(vals, starts))
    exp = np.asarray(segops.segmented_scan(vals, starts))
    assert np.array_equal(got, exp), (got, exp)

    # --- full V-cycle parity (sharded coarsen + contract + refine): ------
    # 2 racing replicas x 4 pipeline shards and 1 x 8, race off; each mesh
    # also with memory-sharded graph storage (pins arrays striped over
    # "model", `dist.graph.ShardedHypergraph`) — still bit-exact
    hg = generate.snn_layered(n_layers=4, width=24, fanout=6, window=8,
                              seed=3)
    r0 = partition(hg, omega=16, delta=64, theta=4)
    for shape in ((2, 4), (1, 8)):
        mesh = jax.make_mesh(shape, ("data", "model"))
        plan = Plan.make(mesh)
        r1 = partition(hg, omega=16, delta=64, theta=4, plan=plan,
                       race=False)
        assert np.array_equal(r0.parts, r1.parts), shape
        assert r0.audit == r1.audit, shape
        assert r0.n_levels == r1.n_levels, shape  # coarsening on-mesh too
        refused = lambda r: [(s.refused_omega, s.refused_delta)
                             for s in r.level_stats]
        assert refused(r0) == refused(r1), shape
        assert any(o for o, _ in refused(r0)[:-1])  # Omega refuses some
        rs = partition(hg, omega=16, delta=64, theta=4, plan=plan,
                       race=False, shard_graph=True)
        assert np.array_equal(r0.parts, rs.parts), ("sharded", shape)
        assert r0.audit == rs.audit, ("sharded", shape)
        assert r0.n_levels == rs.n_levels, ("sharded", shape)
        assert refused(r0) == refused(rs), ("sharded", shape)

    # --- sharded storage really stripes: each device holds 1/4 of the
    # pins lanes on the (2,4) mesh (replicated across the data axis)
    from repro.core.hypergraph import Caps
    from repro.dist import graph as dist_graph
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    plan = Plan.make(mesh)
    caps = Caps.for_host(hg)
    g = dist_graph.sharded_from_host(hg, caps, plan)
    per = -(-caps.p // 4)
    for f in dist_graph.PINS_FIELDS:
        arr = getattr(g.g, f)
        assert arr.shape[0] == per * 4, f
        for sh in arr.addressable_shards:
            assert sh.data.shape[0] == per, f
    assert g.pins_bytes_per_device() * 4 <= 9 * caps.p + 9 * 4  # ~1/4 + pad
    # racing replicas share the one sharded graph: raced run stays valid
    r4 = partition(hg, omega=16, delta=64, theta=4, plan=plan, race=True,
                   race_seed=1, shard_graph=True)
    assert r4.audit["size_ok"] and r4.audit["inbound_ok"]

    # --- ShardCtx.gread/gfull units on a real 8-way stripe ---------------
    mesh8 = jax.make_mesh((8,), ("model",))
    ctx8 = segops.ShardCtx(axis="model", nshards=8, graph_striped=True)
    rng8 = np.random.default_rng(1)
    col = jnp.asarray(rng8.integers(0, 100, 64).astype(np.int32))
    def gbody(c):
        t, ok = ctx8.lanes(64)
        own = ctx8.gread(ctx8.stripe(c), t, ok, -1)
        full = ctx8.gfull(ctx8.stripe(c))
        return ctx8.gather(own), full
    gf = jax.shard_map(gbody, mesh=mesh8, in_specs=(P(),),
                       out_specs=(P(), P()), check_vma=False)
    own8, full8 = jax.jit(gf)(col)
    assert np.array_equal(np.asarray(own8), np.asarray(col))
    assert np.array_equal(np.asarray(full8)[:64], np.asarray(col))

    # --- shard-only mesh (no data axis): racing must be skipped, not run
    # over the pipeline-shard axis (replicas diverging along "model" would
    # corrupt the psum'd pipelines) — parity holds even with race=True
    mesh = jax.make_mesh((8,), ("model",))
    plan = Plan.make(mesh)
    r3 = partition(hg, omega=16, delta=64, theta=4, plan=plan, race=True)
    assert np.array_equal(r0.parts, r3.parts)

    # --- racing replicas: valid audit, never worse than doing nothing ----
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    plan = Plan.make(mesh)
    r2 = partition(hg, omega=16, delta=64, theta=4, plan=plan, race=True,
                   race_seed=1)
    assert r2.audit["size_ok"] and r2.audit["inbound_ok"]
    print("DIST_PARITY_OK", r0.connectivity, r2.connectivity)
""")


@pytest.mark.slow
def test_dist_partition_parity_8dev_subprocess(tmp_path):
    script = tmp_path / "dist_parity.py"
    script.write_text(_MULTIDEV)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST_PARITY_OK" in r.stdout
