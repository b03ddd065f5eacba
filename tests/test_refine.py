"""Refinement: pins matrix, isolation gains, in-sequence gains (exact vs
brute force AND vs a literal Eq. 14/15 oracle), events-based selection vs
step-by-step simulation (paper Sec. VI)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generate, metrics
from repro.core import hypergraph as H
from repro.core import refine as R


def _setup(seed, n=36, e=54, k=4, K=5, kcap=8, omega=11, delta=40):
    rng = np.random.default_rng(seed)
    hg = generate.random_kuniform(n_nodes=n, n_edges=e, k=k, seed=seed,
                                  weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    parts0 = rng.integers(0, K, size=hg.n_nodes).astype(np.int32)
    parts = jnp.asarray(np.pad(parts0, (0, caps.n - hg.n_nodes)))
    params = R.RefineParams(omega=omega, delta=delta, theta=1)
    return hg, caps, d, parts0, parts, params, K, kcap


def test_pins_matrix_oracle():
    hg, caps, d, parts0, parts, params, K, kcap = _setup(0)
    pins, pins_in = R.pins_matrix(d, parts, caps, kcap)
    p_np = np.zeros((kcap, caps.e), np.int32)
    pi_np = np.zeros((kcap, caps.e), np.int32)
    for e in range(hg.n_edges):
        for idx, p in enumerate(hg.edge(e)):
            p_np[parts0[p], e] += 1
            if idx >= hg.edge_nsrc[e]:
                pi_np[parts0[p], e] += 1
    np.testing.assert_array_equal(np.asarray(pins), p_np)
    np.testing.assert_array_equal(np.asarray(pins_in), pi_np)


def test_isolation_gains_match_connectivity_delta():
    hg, caps, d, parts0, parts, params, K, kcap = _setup(1)
    pins, _ = R.pins_matrix(d, parts, caps, kcap)
    move_to, gain_iso, _, _ = R.propose_moves(
        d, parts, pins, caps, kcap, params, jnp.asarray(False), jnp.int32(K))
    mv, gi = np.asarray(move_to), np.asarray(gain_iso)
    conn0 = metrics.connectivity(hg, parts0)
    for n in range(hg.n_nodes):
        if mv[n] >= 0:
            p2 = parts0.copy()
            p2[n] = mv[n]
            assert abs((conn0 - metrics.connectivity(hg, p2)) - gi[n]) < 1e-4


def _sequence(hg, caps, d, parts0, parts, params, K, kcap):
    pins, pins_in = R.pins_matrix(d, parts, caps, kcap)
    move_to, gain_iso, _, _ = R.propose_moves(
        d, parts, pins, caps, kcap, params, jnp.asarray(False), jnp.int32(K))
    seq, _ = R.build_sequence(d, parts, move_to, gain_iso, caps, kcap, params)
    gain_seq = R.inseq_gains(d, parts, pins, move_to, gain_iso, seq, caps,
                             kcap)
    return pins, pins_in, move_to, gain_iso, seq, gain_seq


def literal_eq14_15(hg, parts0, mv, gi, sq, pins_np):
    """The paper's OR-form: used to document where it under-counts."""
    node_off, node_edges, _, _ = hg.incidence()
    out = {}
    for n in range(hg.n_nodes):
        if mv[n] < 0:
            continue
        g = gi[n]
        ps_n, pd_n = parts0[n], mv[n]
        for idx in range(node_off[n], node_off[n + 1]):
            e = node_edges[idx]
            w = hg.edge_w[e]
            earlier = [m for m in hg.edge(e)
                       if m != n and mv[m] >= 0 and sq[m] < sq[n]]
            a_pd = sum(1 for m in earlier if parts0[m] == pd_n)
            b_pd = sum(1 for m in earlier if mv[m] == pd_n)
            a_ps = sum(1 for m in earlier if parts0[m] == ps_n)
            b_ps = sum(1 for m in earlier if mv[m] == ps_n)
            Ppd, Pps = pins_np[pd_n, e], pins_np[ps_n, e]
            c1 = ((a_pd - b_pd == Ppd) and Ppd > 0) or (b_ps > 0 and Pps == 1)
            c2 = ((a_ps - b_ps == Pps - 1) and Pps - 1 > 0) or \
                 (b_pd > 0 and Ppd == 0)
            g += (-w if c1 else 0) + (w if c2 else 0)
        out[n] = g
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_inseq_gains_exact_per_prefix(seed):
    hg, caps, d, parts0, parts, params, K, kcap = _setup(seed)
    _, _, move_to, _, seq, gain_seq = _sequence(
        hg, caps, d, parts0, parts, params, K, kcap)
    mv, sq, gs = np.asarray(move_to), np.asarray(seq), np.asarray(gain_seq)
    order = [n for n in np.argsort(sq[: hg.n_nodes]) if mv[n] >= 0]
    p_cur = parts0.copy()
    conn_prev = metrics.connectivity(hg, parts0)
    for n in order:
        p_cur[n] = mv[n]
        c = metrics.connectivity(hg, p_cur)
        assert abs((conn_prev - c) - gs[n]) < 1e-4
        conn_prev = c


def test_inseq_matches_literal_form_when_single_clause():
    """Where exactly one clause of Eq. 14/15 fires, our exact form equals
    the paper's literal OR-form (regression for the documented deviation)."""
    hg, caps, d, parts0, parts, params, K, kcap = _setup(5)
    pins, _, move_to, gain_iso, seq, gain_seq = _sequence(
        hg, caps, d, parts0, parts, params, K, kcap)
    mv, sq = np.asarray(move_to), np.asarray(seq)
    lit = literal_eq14_15(hg, parts0, mv, np.asarray(gain_iso), sq,
                          np.asarray(pins))
    gs = np.asarray(gain_seq)
    agree = sum(1 for n, v in lit.items() if abs(gs[n] - v) < 1e-4)
    # the OR-form agrees on the large majority of moves; the exact form
    # (ours) diverges precisely where both clauses fire (DESIGN.md §8.6)
    assert agree >= 0.7 * max(len(lit), 1)


@pytest.mark.parametrize("seed", [0, 2, 4])
def test_events_select_bruteforce_best_valid_prefix(seed):
    hg, caps, d, parts0, parts, params, K, kcap = _setup(seed)
    _, pins_in, move_to, _, seq, gain_seq = _sequence(
        hg, caps, d, parts0, parts, params, K, kcap)
    apply_mask, applied_gain = R.events_validity(
        d, parts, pins_in, move_to, seq, gain_seq, caps, kcap, params)
    mv, sq, gs = np.asarray(move_to), np.asarray(seq), np.asarray(gain_seq)
    order = [n for n in np.argsort(sq[: hg.n_nodes]) if mv[n] >= 0]
    p_cur = parts0.copy()
    viol, cum = [], []
    tot = 0.0
    for n in order:
        p_cur[n] = mv[n]
        a = metrics.audit(hg, p_cur.astype(np.int64), params.omega,
                          params.delta)
        viol.append(a["n_size_violations"] + a["n_inbound_violations"])
        tot += gs[n]
        cum.append(tot)
    cands = [t for t in range(len(order)) if viol[t] == 0]
    bt = max(cands, key=lambda t: (cum[t], -t)) if cands else None
    expect = set(order[: bt + 1]) if (bt is not None and cum[bt] > 0) else set()
    got = set(np.where(np.asarray(apply_mask)[: hg.n_nodes])[0])
    assert got == expect
    if expect:
        assert abs(float(applied_gain) - cum[bt]) < 1e-4


def test_refine_step_monotone_and_valid():
    hg, caps, d, parts0, parts, params, K, kcap = _setup(3, omega=12)
    conn0 = metrics.connectivity(hg, parts0)
    p = parts
    for rep in range(3):
        p, g, _ = R.refine_step(d, p, jnp.int32(K), caps, kcap, params,
                                enforce_size=True)
    parts1 = np.asarray(p)[: hg.n_nodes]
    conn1 = metrics.connectivity(hg, parts1)
    assert conn1 <= conn0 + 1e-6


@pytest.mark.parametrize("seed,omega,delta", [
    (0, 14, 40),    # the second half applies once, then stops
    (1, 14, 40),    # nothing applies: each half stops at its first rep
    (2, 24, 80),    # the first half applies once, the second all through
    (3, 16, 60),    # the second half applies four times before its no-op
])
def test_refine_level_early_exit_is_exact(seed, omega, delta):
    """`refine_level` skips each half-level's repetitions after its first
    no-op, and still returns the parts of a plain loop of every
    repetition, bit for bit."""
    n, K, kcap, theta = 48, 5, 8, 12
    rng = np.random.default_rng(seed)
    hg = generate.random_kuniform(n_nodes=n, n_edges=72, k=4, seed=seed,
                                  weighted=True)
    caps = H.Caps.for_host(hg)
    d = H.device_from_host(hg, caps)
    parts = jnp.asarray(np.pad(rng.integers(0, K, size=n).astype(np.int32),
                               (0, caps.n - n)))
    params = R.RefineParams(omega=omega, delta=delta, theta=theta)
    plain_step = jax.jit(R.refine_step_impl,
                         static_argnames=("caps", "kcap", "params"))
    want = parts
    for rep in range(theta):
        want, _, _ = plain_step(d, want, jnp.int32(K), caps=caps, kcap=kcap,
                                params=params,
                                enforce_size=jnp.asarray(rep >= theta // 2))
    log = []
    got, counters = R.refine_level(d, parts, K, caps, kcap, params, log=log)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cnt = dict(zip(R.LEVEL_COUNTERS, np.asarray(counters).tolist()))
    for e in log:
        fresh = e["rep"] in (0, theta // 2)
        after_noop = not fresh and log[e["rep"] - 1]["applied"] == 0
        assert e["skipped"] == after_noop, e
        if e["skipped"]:
            assert (e["applied"], e["proposed"], e["gain"]) == (0, 0, 0.0)
    ran = sum(not e["skipped"] for e in log)
    assert cnt["skipped_reps"] == theta - ran
    assert cnt["noop_reps"] == sum(e["applied"] == 0 for e in log)
    assert cnt["reps"] == theta
