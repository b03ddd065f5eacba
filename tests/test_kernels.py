"""Pallas kernels: shape/dtype sweeps vs pure-jnp oracles (interpret mode),
plus end-to-end equality of the kernel-routed partitioner paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gains.kernel import gains_pallas
from repro.kernels.gains.ref import gains_ref
from repro.kernels.pair_scores.kernel import pair_scores_pallas
from repro.kernels.pair_scores.ref import pair_scores_ref
from repro.kernels.pins_count.kernel import pins_count_pallas
from repro.kernels.pins_count.ref import pins_count_ref


# k > 512 walks several partition chunks; k = 768 also pads the last one,
# so padding ids in [k, 1024) must be remapped past it (ids >= k are padding)
@pytest.mark.parametrize("e,d,k", [(8, 128, 8), (16, 256, 16), (32, 128, 4),
                                   (8, 384, 64), (8, 128, 1024),
                                   (8, 128, 768)])
def test_pins_count_sweep(e, d, k, rng):
    parts = rng.integers(0, k + 1 + k // 4, size=(e, d)).astype(np.int32)
    dst = rng.integers(0, 2, size=(e, d)).astype(np.int32)
    p1, pi1 = pins_count_pallas(jnp.asarray(parts), jnp.asarray(dst), k,
                                te=8, dc=128)
    p2, pi2 = pins_count_ref(jnp.asarray(parts), jnp.asarray(dst), k)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(pi1), np.asarray(pi2))


@pytest.mark.parametrize("n,u,l", [(8, 128, 128), (16, 128, 256),
                                   (8, 256, 384)])
@pytest.mark.parametrize("wdtype", [jnp.float32])
def test_pair_scores_sweep(n, u, l, wdtype, rng):
    nbr = rng.integers(0, 60, size=(n, u)).astype(np.int32)
    nbr[:, u // 2:] = -1
    m = rng.integers(0, 60, size=(n, l)).astype(np.int32)
    m[:, int(l * 0.8):] = -2
    w = rng.random((n, l)).astype(np.float32)
    dd = rng.integers(0, 2, size=(n, l)).astype(np.int32)
    e1, i1 = pair_scores_pallas(*map(jnp.asarray, (nbr, m, w, dd)),
                                tn=8, lc=128)
    e2, i2 = pair_scores_ref(*map(jnp.asarray, (nbr, m, w, dd)))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# k = 2176 walks two 2048-lane chunks, the second mostly padding; h = 72
# stages its slots in two DMA rounds (64 + 8)
@pytest.mark.parametrize("n,h,e,k", [(8, 8, 16, 8), (16, 16, 64, 16),
                                     (8, 4, 32, 128), (8, 8, 16, 2176),
                                     (8, 72, 96, 16)])
def test_gains_sweep(n, h, e, k, rng):
    inc = rng.integers(0, e, size=(n * h,)).astype(np.int32)
    w = rng.random((n, h)).astype(np.float32)
    # dead slots (w == 0, as a node below the degree bound pads its row)
    # are skipped by the kernel and must leave the sums unchanged
    w[rng.random((n, h)) < 0.3] = 0.0
    pnz = (rng.random((e, k)) > 0.5).astype(np.float32)
    c1 = gains_pallas(jnp.asarray(inc), jnp.asarray(w), jnp.asarray(pnz), h=h)
    c2 = gains_ref(jnp.asarray(inc), jnp.asarray(w), jnp.asarray(pnz), h)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)


def test_kernel_routed_partitioner_matches_pure_jax():
    from repro.core import generate
    from repro.core.partitioner import partition
    hg = generate.snn_smallworld(n_nodes=90, fanout=5, seed=11)
    r0 = partition(hg, omega=12, delta=40, theta=2)
    r1 = partition(hg, omega=12, delta=40, theta=2, use_kernels=True)
    np.testing.assert_array_equal(r0.parts, r1.parts)
    assert r0.audit["size_ok"] and r0.audit["inbound_ok"]


def _cond_score_slots(d, nbrs, pairs, caps):
    """The exact `use_kernels=True` dispatch from `coarsen.propose`."""
    import jax
    from repro.core.coarsen import score_slots
    from repro.kernels.pair_scores import ops as ps_ops
    return jax.lax.cond(
        ps_ops.fits_kernel(d, nbrs, pairs, caps),
        lambda: ps_ops.score_slots_kernel(d, nbrs, pairs, caps),
        lambda: score_slots(d, nbrs, pairs, caps))


def test_pair_scores_cond_inside_tile_bounds(rng):
    """Graph within the level-0 tile bounds: the kernel branch is taken and
    must agree with `score_slots` (eta to fp tolerance — the kernel sums in
    a different order — inter exactly)."""
    from repro.core import generate
    from repro.core import hypergraph as H
    from repro.core.coarsen import score_slots
    from repro.kernels.pair_scores import ops as ps_ops

    hg = generate.random_kuniform(36, 50, 5, seed=4, n_src=2, weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    nbrs = H.build_neighbors(pairs, d, caps)
    assert bool(ps_ops.fits_kernel(d, nbrs, pairs, caps))
    eta_c, inter_c = _cond_score_slots(d, nbrs, pairs, caps)
    eta_k, inter_k = ps_ops.score_slots_kernel(d, nbrs, pairs, caps)
    eta_s, inter_s = score_slots(d, nbrs, pairs, caps)
    # cond took the kernel branch bit-for-bit
    np.testing.assert_array_equal(np.asarray(eta_c), np.asarray(eta_k))
    np.testing.assert_array_equal(np.asarray(inter_c), np.asarray(inter_k))
    np.testing.assert_allclose(np.asarray(eta_c), np.asarray(eta_s),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(inter_c), np.asarray(inter_s))


def test_pair_scores_cond_outside_tile_bounds_falls_back():
    """Graph whose per-node traversal/neighborhood exceed the (shrunken)
    level-0 tile bounds: `fits_kernel` must reject and the `lax.cond`
    fallback branch must produce bit-identical (eta, inter) to
    `score_slots` — the guard the coarse levels rely on when merged
    neighborhoods outgrow the level-0 caps."""
    import dataclasses
    from repro.core import generate
    from repro.core import hypergraph as H
    from repro.core.coarsen import score_slots
    from repro.kernels.pair_scores import ops as ps_ops

    # one 140-pin edge: every pin sees 139 unique neighbors > the 128-wide
    # tile that caps with u0 = l0 = 1 round up to
    hg = generate.random_kuniform(200, 3, 140, seed=1, n_src=2,
                                  weighted=True)
    caps0 = H.Caps.for_host(hg)
    caps = dataclasses.replace(caps0, u0=1, l0=1)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    nbrs = H.build_neighbors(pairs, d, caps)
    assert not bool(ps_ops.fits_kernel(d, nbrs, pairs, caps))
    eta_c, inter_c = _cond_score_slots(d, nbrs, pairs, caps)
    eta_s, inter_s = score_slots(d, nbrs, pairs, caps)
    np.testing.assert_array_equal(np.asarray(eta_c), np.asarray(eta_s))
    np.testing.assert_array_equal(np.asarray(inter_c), np.asarray(inter_s))
