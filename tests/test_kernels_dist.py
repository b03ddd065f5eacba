"""Stripe-local Pallas kernel dispatch (`repro.kernels` + the sharded
hot-loop wrappers) — boundary behaviour, mutation sensitivity, and
kernel-vs-segment parity under `shard_map`.

Fast tests run everywhere; the 8-device variants mirror
tests/test_dist_partition.py: a subprocess forces 8 host devices, and the
in-process variant picks up CI's forced-fan-out step (XLA_FLAGS already set
before jax import)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def test_interpret_policy_env_override(monkeypatch):
    """`pallas_interpret` follows the backend alone: interpret on CPU,
    compiled on TPU, and an error on any other backend."""
    import jax
    from repro.kernels import pallas_interpret

    assert jax.default_backend() == "cpu"
    assert pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    for other in ("gpu", "cuda", "rocm"):
        monkeypatch.setattr(jax, "default_backend", lambda o=other: o)
        with pytest.raises(RuntimeError, match="neither"):
            pallas_interpret()


def _pair_setup(k_pins):
    """One k-uniform edge: every pin sees exactly k_pins - 1 unique
    neighbors and k_pins - 1 traversal entries — count == bound when
    k_pins - 1 == 128 with u0 = l0 = 1 (tile bounds round up to 128)."""
    import dataclasses
    from repro.core import generate
    from repro.core import hypergraph as H

    hg = generate.random_kuniform(200, 1, k_pins, seed=7, n_src=2,
                                  weighted=True)
    caps = dataclasses.replace(H.Caps.for_host(hg), u0=1, l0=1)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    nbrs = H.build_neighbors(pairs, d, caps)
    return d, nbrs, pairs, caps


def test_fits_kernel_boundary_exact():
    """The dispatch flips exactly at the tile bound: 128 unique neighbors
    (== bound) routes to the kernel, 129 (== bound + 1) falls back — and
    the `lax.cond` output is bit-identical to the branch it claims to have
    taken in both cases."""
    import jax
    from repro.core.coarsen import score_slots
    from repro.kernels.pair_scores import ops as ps_ops

    def cond_dispatch(d, nbrs, pairs, caps):
        return jax.lax.cond(
            ps_ops.fits_kernel(d, nbrs, pairs, caps),
            lambda: ps_ops.score_slots_kernel(d, nbrs, pairs, caps),
            lambda: score_slots(d, nbrs, pairs, caps))

    # count == bound: kernel branch
    d, nbrs, pairs, caps = _pair_setup(129)
    assert ps_ops.tile_bounds(caps) == (128, 128)
    assert bool(ps_ops.fits_kernel(d, nbrs, pairs, caps))
    eta_c, inter_c = cond_dispatch(d, nbrs, pairs, caps)
    eta_k, inter_k = ps_ops.score_slots_kernel(d, nbrs, pairs, caps)
    np.testing.assert_array_equal(np.asarray(eta_c), np.asarray(eta_k))
    np.testing.assert_array_equal(np.asarray(inter_c), np.asarray(inter_k))

    # count == bound + 1: fallback branch, bit-identical to the segments
    d, nbrs, pairs, caps = _pair_setup(130)
    assert ps_ops.tile_bounds(caps) == (128, 128)
    assert not bool(ps_ops.fits_kernel(d, nbrs, pairs, caps))
    eta_c, inter_c = cond_dispatch(d, nbrs, pairs, caps)
    eta_s, inter_s = score_slots(d, nbrs, pairs, caps)
    np.testing.assert_array_equal(np.asarray(eta_c), np.asarray(eta_s))
    np.testing.assert_array_equal(np.asarray(inter_c), np.asarray(inter_s))


def test_stripe_tile_scatter_mutation_is_caught(monkeypatch):
    """Mutation check: corrupting the stripe-tile layout (an undersized row
    tile silently dropping the tail nodes' scatters) must be caught by the
    kernel-vs-segment oracle comparison — guards against a broken stripe
    scatter passing parity by accident."""
    from repro.core import generate
    from repro.core import hypergraph as H
    from repro.core.coarsen import score_slots
    from repro.kernels.pair_scores import ops as ps_ops

    hg = generate.random_kuniform(36, 50, 5, seed=4, n_src=2, weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    nbrs = H.build_neighbors(pairs, d, caps)
    eta_s, _ = score_slots(d, nbrs, pairs, caps)
    eta_ok, _ = ps_ops.score_slots_kernel(d, nbrs, pairs, caps)
    np.testing.assert_allclose(np.asarray(eta_ok), np.asarray(eta_s),
                               atol=1e-5)

    healthy = ps_ops.stripe_rows(caps, 1)
    assert healthy - 8 < caps.n  # the mutation really drops live rows
    monkeypatch.setattr(ps_ops, "stripe_rows", lambda c, s: healthy - 8)
    eta_bad, _ = ps_ops.score_slots_kernel(d, nbrs, pairs, caps)
    assert not np.allclose(np.asarray(eta_bad), np.asarray(eta_s),
                           atol=1e-5)


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import generate
    from repro.core import hypergraph as H
    from repro.core import refine as R
    from repro.core.coarsen import score_slots
    from repro.core.partitioner import partition
    from repro.dist.graph import graph_pspecs
    from repro.dist.sharding import Plan
    from repro.kernels.gains import ops as g_ops
    from repro.kernels.pair_scores import ops as ps_ops
    from repro.utils import segops

    assert len(jax.devices()) == 8

    # --- stripe-local pair_scores under shard_map: bit-identical to the
    # single-device kernel, fp-close to the segment oracle ----------------
    hg = generate.random_kuniform(36, 50, 5, seed=4, n_src=2, weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    nbrs = H.build_neighbors(pairs, d, caps)
    assert bool(ps_ops.fits_kernel(d, nbrs, pairs, caps))
    eta0, inter0 = ps_ops.score_slots_kernel(d, nbrs, pairs, caps)
    eta_seg, inter_seg = score_slots(d, nbrs, pairs, caps)

    mesh = jax.make_mesh((8,), ("model",))
    ctx = segops.ShardCtx(axis="model", nshards=8)
    def ps_body(d_):
        pidx, pok = ctx.lanes(caps.pairs)
        prs = H.build_pairs(d_, caps, idx=pidx, idx_ok=pok, ctx=ctx)
        nb = H.build_neighbors(prs, d_, caps, ctx)
        fits = ps_ops.fits_kernel(d_, nb, prs, caps, ctx)
        eta, inter = ps_ops.score_slots_kernel(d_, nb, prs, caps, ctx)
        return fits, eta, inter
    ps_fn = jax.jit(jax.shard_map(
        ps_body, mesh=mesh, in_specs=(graph_pspecs(False),),
        out_specs=(P(), P(), P()), check_vma=False))
    fits8, eta8, inter8 = ps_fn(d)
    assert bool(fits8)
    assert np.array_equal(np.asarray(eta8), np.asarray(eta0))
    assert np.array_equal(np.asarray(inter8), np.asarray(inter0))
    np.testing.assert_allclose(np.asarray(eta8), np.asarray(eta_seg),
                               atol=1e-5)
    assert np.array_equal(np.asarray(inter8), np.asarray(inter_seg))
    print("PAIR_SCORES_SHARD_OK")

    # --- stripe-local gains under shard_map ------------------------------
    K, kcap = 5, 8
    rng = np.random.default_rng(3)
    parts = jnp.asarray(np.pad(
        rng.integers(0, K, hg.n_nodes).astype(np.int32),
        (0, caps.n - hg.n_nodes)))
    pins0, _ = R.pins_matrix(d, parts, caps, kcap)
    conn0 = g_ops.conn_weights(d, parts, pins0, caps, kcap)
    def g_body(d_, parts_):
        pins, _ = R.pins_matrix(d_, parts_, caps, kcap, ctx)
        return g_ops.conn_weights(d_, parts_, pins, caps, kcap, ctx)
    g_fn = jax.jit(jax.shard_map(
        g_body, mesh=mesh, in_specs=(graph_pspecs(False), P()),
        out_specs=P(), check_vma=False))
    conn8 = g_fn(d, parts)
    assert np.array_equal(np.asarray(conn8), np.asarray(conn0))
    # segment-path oracle (the _conn_segments computation, single device)
    t = jnp.arange(caps.p, dtype=jnp.int32)
    live = t < d.n_pins
    n_of = segops.rows_from_offsets(d.node_off, caps.p, caps.n)
    e = jnp.clip(d.node_edges, 0, caps.e - 1)
    w = jnp.where(live, d.edge_w[e], 0.0)
    contrib = w[:, None] * (pins0[:, e].T > 0)
    conn_seg = jax.ops.segment_sum(
        contrib, jnp.where(live, n_of, caps.n),
        num_segments=caps.n + 1)[: caps.n]
    np.testing.assert_allclose(np.asarray(conn8), np.asarray(conn_seg),
                               atol=1e-5)
    print("GAINS_SHARD_OK")

    # --- full V-cycle: kernels-on sharded vs kernels-on single device is
    # bit-exact on (2,4) and (1,8), kernels demonstrably fire on the
    # sharded path, and the per-level dispatch branch is mesh-independent
    hg2 = generate.snn_layered(n_layers=4, width=24, fanout=6, window=8,
                               seed=3)
    kw = dict(omega=16, delta=64, theta=4, use_kernels=True)
    r0 = partition(hg2, **kw)
    assert sum(r0.kernel_path["coarsen"]) > 0
    assert sum(r0.kernel_path["refine"]) > 0
    # the mesh runs all theta repetitions of a level, the single device only
    # those its early exit leaves; the branch per level is the same
    ran = [kw["theta"] - s.skipped_reps for s in r0.level_stats]

    def as_one_chip(kp):
        for key in ("refine", "pins"):
            assert all(h in (0, kw["theta"]) for h in kp[key]), kp
        return dict(kp, **{key: [h // kw["theta"] * n
                                 for h, n in zip(kp[key], ran)]
                           for key in ("refine", "pins")})

    for shape in ((2, 4), (1, 8)):
        plan = Plan.make(jax.make_mesh(shape, ("data", "model")))
        r1 = partition(hg2, **kw, plan=plan, race=False)
        assert np.array_equal(r0.parts, r1.parts), shape
        assert r0.audit == r1.audit, shape
        assert r0.n_levels == r1.n_levels, shape
        # kernel_path_taken > 0 for the sharded levels, and the branch
        # taken per level matches the single-device run exactly
        assert as_one_chip(r1.kernel_path) == r0.kernel_path, shape
        assert sum(r1.kernel_path["coarsen"]) > 0, shape
        assert sum(r1.kernel_path["refine"]) > 0, shape
    # memory-sharded graph storage: same contract
    plan = Plan.make(jax.make_mesh((2, 4), ("data", "model")))
    rs = partition(hg2, **kw, plan=plan, race=False, shard_graph=True)
    assert np.array_equal(r0.parts, rs.parts)
    assert as_one_chip(rs.kernel_path) == r0.kernel_path
    print("KERNELS_DIST_PARITY_OK")
""")


@pytest.mark.slow
def test_kernels_dist_parity_8dev_subprocess(tmp_path):
    script = tmp_path / "kernels_dist_parity.py"
    script.write_text(_SHARDED)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "KERNELS_DIST_PARITY_OK" in r.stdout


@pytest.mark.slow
def test_kernels_dist_parity_inprocess_8dev():
    """Runs only when the session itself was launched with 8 forced host
    devices (CI's forced-fan-out step): kernels-on full-V-cycle parity on
    (2, 4) + coverage assertion, without the subprocess."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.core import generate
    from repro.core.partitioner import partition
    from repro.dist.sharding import Plan

    hg = generate.snn_layered(n_layers=4, width=24, fanout=6, window=8,
                              seed=3)
    kw = dict(omega=16, delta=64, theta=4, use_kernels=True)
    r0 = partition(hg, **kw)
    plan = Plan.make(jax.make_mesh((2, 4), ("data", "model")))
    r1 = partition(hg, **kw, plan=plan, race=False)
    assert np.array_equal(r0.parts, r1.parts)
    assert r0.audit == r1.audit
    # the mesh runs all theta repetitions of a level, the single device only
    # those its early exit leaves; the branch per level is the same
    ran = [kw["theta"] - s.skipped_reps for s in r0.level_stats]
    assert r1.kernel_path["coarsen"] == r0.kernel_path["coarsen"]
    for key in ("refine", "pins"):
        assert all(h in (0, kw["theta"]) for h in r1.kernel_path[key])
        assert [h // kw["theta"] * n for h, n in
                zip(r1.kernel_path[key], ran)] == r0.kernel_path[key]
    assert sum(r1.kernel_path["coarsen"]) > 0
    assert sum(r1.kernel_path["refine"]) > 0
