"""Hypothesis property tests on system invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (declared in [test] extras; "
           "pip install hypothesis)")
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import generate, metrics
from repro.core import hypergraph as H
from repro.core import refine as R
from repro.core.coarsen import CoarsenParams, coarsen_step
from repro.core.contract import contract
from repro.core.matching import match_pseudoforest
from repro.utils import segops

from test_matching import brute_force, matched_value, proposal_graph

SET = settings(max_examples=12, deadline=None,
               suppress_health_check=[HealthCheck.too_slow])

IMAX = 2**31 - 1


@given(n=st.integers(8, 40), e=st.integers(5, 40), k=st.integers(2, 6),
       seed=st.integers(0, 1000))
@SET
def test_pair_expansion_complete_and_exact(n, e, k, seed):
    hg = generate.random_kuniform(n, e, min(k, n), seed=seed)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    pairs = H.build_pairs(d, caps)
    got = set()
    pe, pn, pm, pv = map(np.asarray, (pairs.edge, pairs.n, pairs.m,
                                      pairs.valid))
    for i in range(len(pv)):
        if pv[i]:
            got.add((int(pe[i]), int(pn[i]), int(pm[i])))
    exp = set()
    for ei in range(hg.n_edges):
        pins = hg.edge(ei)
        for a in pins:
            for b in pins:
                if a != b:
                    exp.add((ei, int(a), int(b)))
    assert got == exp


@given(n=st.integers(12, 60), fanout=st.integers(3, 8),
       omega=st.integers(2, 12), seed=st.integers(0, 100))
@SET
def test_one_coarsen_level_always_valid(n, fanout, omega, seed):
    hg = generate.snn_smallworld(n_nodes=n, fanout=fanout, seed=seed)
    # feasibility precondition (paper Sec. II-B assumes a valid solution
    # exists): Delta must cover the largest single-node inbound set
    _, _, _, node_nin = hg.incidence()
    delta = max(2 * fanout, 8, int(node_nin.max(initial=0)))
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    match, n_pairs, _ = coarsen_step(
        d, caps, CoarsenParams(omega=omega, delta=delta, n_cands=2))
    m = np.asarray(match)[:n]
    # matching is an involution on matched nodes
    for a in range(n):
        if m[a] >= 0:
            assert m[m[a]] == a and m[a] != a
    d2, gamma = contract(d, match, caps)
    g = np.asarray(gamma)[:n]
    sizes, inbound = metrics.partition_loads(hg, g)
    assert (sizes <= omega).all()
    assert (inbound <= delta).all()
    # gamma is a surjection onto [0, n_new)
    assert set(g.tolist()) == set(range(int(d2.n_nodes)))


@given(vals=st.lists(st.floats(-100, 100, width=32), min_size=2,
                     max_size=50),
       seed=st.integers(0, 100))
@SET
def test_segmented_scan_property(vals, seed):
    rng = np.random.default_rng(seed)
    v = np.asarray(vals, np.float32)
    starts = rng.random(len(v)) < 0.3
    starts[0] = True
    out = np.asarray(segops.segmented_scan(jnp.asarray(v),
                                           jnp.asarray(starts)))
    i0 = 0
    for i in range(len(v)):
        if starts[i]:
            i0 = i
        np.testing.assert_allclose(out[i], v[i0:i + 1].sum(), rtol=1e-4,
                                   atol=1e-4)


@given(n=st.integers(10, 40), e=st.integers(8, 50), k=st.integers(2, 4),
       kparts=st.integers(2, 6), seed=st.integers(0, 1000),
       rank_seed=st.integers(0, 3))
@SET
def test_build_sequence_properties(n, e, k, kparts, seed, rank_seed):
    """`build_sequence` invariants, for the identity and arbitrary tie-break
    permutations (replica racing uses the latter):
      * mover `seq` values form a contiguous permutation 0..n_movers-1
      * non-movers (and capacity padding) sit at IMAX
      * the post-cut `pred` relation is acyclic, and within a chain
        `seq[pred[x]] == seq[x] - 1`."""
    rng = np.random.default_rng(seed)
    hg = generate.random_kuniform(n, e, min(k, n), seed=seed, weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    kcap = 8
    parts0 = rng.integers(0, kparts, size=hg.n_nodes).astype(np.int32)
    parts = jnp.asarray(np.pad(parts0, (0, caps.n - hg.n_nodes)))
    params = R.RefineParams(omega=max(3, n // 2), delta=4 * e)
    pins, _ = R.pins_matrix(d, parts, caps, kcap)
    move_to, gain_iso, _, _ = R.propose_moves(
        d, parts, pins, caps, kcap, params, jnp.asarray(False),
        jnp.int32(kparts))
    tie_rank = None
    if rank_seed > 0:
        tie_rank = jnp.asarray(np.random.default_rng(rank_seed)
                               .permutation(caps.n).astype(np.int32))
    seq, n_movers, aux = R.build_sequence(
        d, parts, move_to, gain_iso, caps, kcap, params,
        tie_rank=tie_rank, with_aux=True)
    mv = np.asarray(move_to)[: hg.n_nodes]
    sq = np.asarray(seq)
    nm = int(n_movers)
    assert sorted(sq[: hg.n_nodes][mv >= 0].tolist()) == list(range(nm))
    assert (sq[: hg.n_nodes][mv < 0] == IMAX).all()
    assert (sq[hg.n_nodes:] == IMAX).all()
    pred = np.asarray(aux["pred"])
    for x in range(caps.n):
        p, steps = x, 0
        while pred[p] >= 0:
            p = pred[p]
            steps += 1
            assert steps <= caps.n, "pred cycle survived cutting"
    for x in range(hg.n_nodes):
        if mv[x] >= 0 and pred[x] >= 0:
            assert sq[pred[x]] == sq[x] - 1


@given(n=st.integers(3, 9), seed=st.integers(0, 10_000))
@SET
def test_matching_total_equals_bruteforce_dp(n, seed):
    """On invariant-respecting round-1 proposal graphs (symmetric eta,
    larger-id tie-break), `match_pseudoforest`'s matched total equals the
    exact max-weight matching (brute-force over edge subsets), and the
    matching only uses proposed edges mutually."""
    rng = np.random.default_rng(seed)
    target, score = proposal_graph(rng, n)
    m = np.asarray(match_pseudoforest(
        jnp.asarray(target), jnp.asarray(score), jnp.ones(n, bool)))
    for a in range(n):
        if m[a] >= 0:
            assert m[m[a]] == a and m[a] != a
            assert target[a] == m[a] or target[m[a]] == a
    assert abs(matched_value(target, score, m)
               - brute_force(target, score)) < 1e-5


@given(n=st.integers(2, 40), seed=st.integers(0, 10_000),
       p_dead=st.floats(0.0, 0.6))
@SET
def test_matching_mutual_and_live_on_functional_graphs(n, seed, p_dead):
    """On arbitrary functional graphs (broken invariants, long cycles) the
    output is always a mutual involution and never pairs dead
    (`live=False`) nodes."""
    rng = np.random.default_rng(seed)
    target = rng.integers(-1, n, size=n).astype(np.int32)
    target[target == np.arange(n)] = -1
    score = (rng.random(n) * 10).astype(np.float32)
    live = rng.random(n) >= p_dead
    m = np.asarray(match_pseudoforest(
        jnp.asarray(target), jnp.asarray(score), jnp.asarray(live)))
    for a in range(n):
        if m[a] >= 0:
            assert m[m[a]] == a and m[a] != a
            assert live[a] and live[m[a]]
            assert target[a] == m[a] or target[m[a]] == a
    assert (m[~live] == -1).all()


@given(seed=st.integers(0, 50), k=st.integers(2, 5))
@SET
def test_connectivity_lower_bound_cutnet(seed, k):
    """Conn >= cut-net always; equal iff every cut edge spans 2 parts."""
    rng = np.random.default_rng(seed)
    hg = generate.random_kuniform(24, 30, 4, seed=seed, weighted=True)
    parts = rng.integers(0, k, size=hg.n_nodes)
    assert metrics.connectivity(hg, parts) >= metrics.cut_net(hg, parts) - 1e-6


@given(n_per=st.integers(4, 48), hi1=st.integers(1, 8), hi2=st.integers(1, 6),
       seed=st.integers(0, 1000))
@SET
def test_dist_sort_stable_and_matches_lexsort(n_per, hi1, hi2, seed):
    """`ShardCtx.sort_by` on duplicate-heavy random multi-key columns is
    the stable lexicographic sort: field-by-field equal to the numpy
    lexsort oracle, and equal keys preserve payload order (the threaded
    global-rank tie key). Runs on however many devices the session sees —
    1 locally (degenerate path), 8 in CI's forced-fan-out step (the real
    exchange)."""
    import jax
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    n = n_per * n_dev
    rng = np.random.default_rng(seed)
    k1 = rng.integers(0, hi1, n).astype(np.int32)
    k2 = rng.integers(0, hi2, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)

    mesh = jax.make_mesh((n_dev,), ("model",))
    ctx = segops.ShardCtx(axis="model", nshards=n_dev)

    def body(a, b, p):
        (s1, s2), (sp,) = ctx.sort_by([a, b], [p])
        return s1, s2, sp

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                              out_specs=(P(), P(), P()), check_vma=False))
    s1, s2, sp = map(np.asarray, f(jnp.asarray(k1), jnp.asarray(k2),
                                   jnp.asarray(pay)))
    order = np.lexsort((pay, k2, k1))
    np.testing.assert_array_equal(s1, k1[order])
    np.testing.assert_array_equal(s2, k2[order])
    np.testing.assert_array_equal(sp, pay[order])  # stability: pay == rank
