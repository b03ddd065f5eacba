"""Distributed sample sort (`repro.dist.sort` via `ShardCtx.sort_by`) vs
the gathered stable `lax.sort` and a numpy lexsort oracle.

Contract under test: bit-identity. The sort threads a global-rank tie key,
so every extended key is unique and the bucketed/exchanged order *is* the
stable order of the original keys — on any mesh, through both the
all_to_all exchange path and the capacity-overflow gathered fallback.

The in-process tests run on however many devices the session sees (1 on a
plain run — the degenerate local path; 8 in CI's forced-fan-out step, which
exercises the real exchange). The subprocess test forces 8 host devices and
additionally runs the mutation demo: dropping the global-rank tie key must
be *caught* by the stability oracle (equal keys then merge in buffer order,
not stripe order)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils import segops

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _mesh_sort(keys_np, pays_np, striped=False, with_stats=False, **kw):
    """Run ShardCtx.sort_by under shard_map on a (n,)-model mesh over all
    visible devices; returns full sorted columns (+ fell_back)."""
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("model",))
    ctx = segops.ShardCtx(axis="model", nshards=n)
    keys = [jnp.asarray(k) for k in keys_np]
    pays = [jnp.asarray(p) for p in pays_np]
    nk = len(keys)

    def body(*cols):
        ks, ps = list(cols[:nk]), list(cols[nk:])
        if striped or with_stats or kw:
            from repro.dist import sort as dist_sort
            ks = [ctx.stripe(c) for c in ks]
            ps = [ctx.stripe(c) for c in ps]
            out = dist_sort.sample_sort_stripes(ctx, ks, ps,
                                                with_stats=with_stats, **kw)
            ko, po = out[0], out[1]
            res = [ctx.gather(c) for c in ko + po]
            if with_stats:
                return (*res, out[2])
            return tuple(res)
        ko, po = ctx.sort_by(ks, ps)  # replicated in / replicated out
        return (*ko, *po)

    n_out = nk + len(pays) + (1 if with_stats else 0)
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=tuple(P() for _ in keys + pays),
                              out_specs=tuple(P() for _ in range(n_out)),
                              check_vma=False))
    out = [np.asarray(o) for o in f(*keys, *pays)]
    if with_stats:
        return out[:-1], bool(out[-1].reshape(-1)[0])
    return out


def _oracle(keys_np, pays_np):
    """Stable multi-key sort oracle: np.lexsort (stable, last key primary)."""
    order = np.lexsort(tuple(reversed([np.asarray(k) for k in keys_np])))
    return [np.asarray(c)[order] for c in list(keys_np) + list(pays_np)]


def _assert_cols_equal(got, exp, names=None):
    for i, (g, e) in enumerate(zip(got, exp)):
        np.testing.assert_array_equal(
            g, e, err_msg=f"column {names[i] if names else i}")


def test_dist_sort_matches_lexsort_oracle():
    rng = np.random.default_rng(0)
    n = 64 * len(jax.devices())
    k1 = rng.integers(0, 6, n).astype(np.int32)       # duplicate-heavy
    k2 = rng.integers(0, 4, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)
    got = _mesh_sort([k1, k2], [pay])
    _assert_cols_equal(got, _oracle([k1, k2], [pay]), ["k1", "k2", "pay"])
    # and bitwise against the gathered stable lax.sort
    (e1, e2), (ep,) = segops.sort_by([jnp.asarray(k1), jnp.asarray(k2)],
                                     [jnp.asarray(pay)])
    _assert_cols_equal(got, [np.asarray(e1), np.asarray(e2), np.asarray(ep)])


def test_dist_sort_striped_in_out_matches_gathered():
    rng = np.random.default_rng(1)
    n = 32 * len(jax.devices())
    k1 = rng.integers(-100, 100, n).astype(np.int32)
    k2 = rng.integers(0, 3, n).astype(np.int32)
    p1 = rng.integers(0, 2**20, n).astype(np.int32)
    got = _mesh_sort([k1, k2], [p1], striped=True)
    exp_k, exp_p = segops.sort_by([jnp.asarray(k1), jnp.asarray(k2)],
                                  [jnp.asarray(p1)])
    _assert_cols_equal(got, [np.asarray(c) for c in list(exp_k) + list(exp_p)])


def test_dist_sort_stability_equal_keys_preserve_payload_order():
    rng = np.random.default_rng(2)
    n = 16 * len(jax.devices())
    key = rng.integers(0, 3, n).astype(np.int32)  # tiny key space: many ties
    pay = np.arange(n, dtype=np.int32)
    got_key, got_pay = _mesh_sort([key], [pay])
    for v in np.unique(key):
        grp = got_pay[got_key == v]
        assert np.all(np.diff(grp) > 0), (v, grp)  # input order preserved


def test_dist_sort_float_total_order_edge_cases():
    """-0.0/+0.0 and NaN placement must agree between the gathered and
    distributed sorts (the f32_sort_key canonicalization contract)."""
    rng = np.random.default_rng(3)
    n = 32 * len(jax.devices())
    pool = np.array([0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf,
                     1.5, -1.5, 2**-126, -(2**-126)], np.float32)
    kf = pool[rng.integers(0, len(pool), n)]
    pay = np.arange(n, dtype=np.int32)
    got_key, got_pay = _mesh_sort([kf], [pay])
    (ek,), (ep,) = segops.sort_by([jnp.asarray(kf)], [jnp.asarray(pay)])
    # bitwise: original NaN payloads / zero signs survive in sorted output
    assert np.array_equal(got_key.view(np.uint32), np.asarray(ek).view(np.uint32))
    assert np.array_equal(got_pay, np.asarray(ep))


def test_dist_sort_skew_falls_back_and_stays_exact():
    """Adversarial skew overflows the static exchange capacity -> the
    uniform gathered branch runs; result must stay bit-identical. Uniform
    input takes the exchange path (fell_back False) on a real mesh."""
    n_dev = len(jax.devices())
    rng = np.random.default_rng(4)
    n = 512 * n_dev
    uni = rng.integers(-2**30, 2**30, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)
    got, fb_uni = _mesh_sort([uni], [pay], with_stats=True)
    _assert_cols_equal(got, _oracle([uni], [pay]))
    rev = np.sort(uni)[::-1].copy()
    got, fb_rev = _mesh_sort([rev], [pay], with_stats=True)
    _assert_cols_equal(got, _oracle([rev], [pay]))
    if n_dev >= 8:
        assert not fb_uni          # exchange path actually exercised
        assert fb_rev              # fallback path actually exercised


def test_dist_sort_mutation_dropping_tie_rank_is_caught():
    """Mutation demo (repo convention): without the global-rank tie key,
    equal keys merge in buffer order instead of stripe order — the
    stability oracle must catch it."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for cross-shard duplicates")
    rng = np.random.default_rng(5)
    n = 64 * len(jax.devices())
    # moderate key cardinality: duplicates span every shard but the sort
    # stays on the exchange path (all-equal keys would overflow into the
    # gathered fallback, which is stable regardless of the tie key)
    key = rng.integers(0, 4 * len(jax.devices()), n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)
    (gk, gp), fb = _mesh_sort([key], [pay], with_stats=True,
                              _tie_rank=False)
    assert not fb
    exp = _oracle([key], [pay])
    assert not np.array_equal(gp, exp[1]), \
        "mutation not caught: tie-rank drop left the stable order intact"
    # control: with the tie key the same input is exactly the stable order
    (gk, gp), _ = _mesh_sort([key], [pay], with_stats=True)
    _assert_cols_equal([gk, gp], exp)


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.utils import segops

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(0)

    def mesh_sort(mesh, ctx, keys_np, pays_np, **kw):
        keys = [jnp.asarray(k) for k in keys_np]
        pays = [jnp.asarray(p) for p in pays_np]
        nk = len(keys)
        def body(*cols):
            from repro.dist import sort as dist_sort
            ks = [ctx.stripe(c) for c in cols[:nk]]
            ps = [ctx.stripe(c) for c in cols[nk:]]
            out = dist_sort.sample_sort_stripes(ctx, ks, ps,
                                                with_stats=True, **kw)
            return (*[ctx.gather(c) for c in out[0] + out[1]], out[2])
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=tuple(P() for _ in keys + pays),
            out_specs=tuple(P() for _ in range(nk + len(pays) + 1)),
            check_vma=False))
        out = [np.asarray(o) for o in f(*keys, *pays)]
        return out[:-1], bool(out[-1].reshape(-1)[0])

    # both acceptance meshes: model axis of 4 (with a 2-replica data axis
    # present, as in the V-cycle) and of 8
    for shape, axes in (((2, 4), ("data", "model")), ((1, 8), ("data", "model"))):
        mesh = jax.make_mesh(shape, axes)
        s = shape[1]
        ctx = segops.ShardCtx(axis="model", nshards=s)
        n = 128 * s
        for trial in range(3):
            cols = [rng.integers(0, [6, 2**30, 12][trial], n).astype(np.int32)
                    for _ in range(2)]
            kf = rng.choice(np.array([0.0, -0.0, 1.0, np.nan, np.inf],
                                     np.float32), n)
            pay = np.arange(n, dtype=np.int32)
            got, fb = mesh_sort(mesh, ctx, [cols[0], kf, cols[1]], [pay])
            ek, ep = segops.sort_by(
                [jnp.asarray(cols[0]), jnp.asarray(kf), jnp.asarray(cols[1])],
                [jnp.asarray(pay)])
            for g, e in zip(got, list(ek) + list(ep)):
                e = np.asarray(e)
                if e.dtype.kind == "f":
                    assert np.array_equal(g.view(np.uint32),
                                          e.view(np.uint32)), (shape, trial)
                else:
                    assert np.array_equal(g, e), (shape, trial)

    # fallback + exchange paths both exact, and both actually taken
    mesh = jax.make_mesh((1, 8), ("data", "model"))
    ctx = segops.ShardCtx(axis="model", nshards=8)
    n = 512 * 8
    uni = rng.integers(-2**30, 2**30, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int32)
    (gk, gp), fb = mesh_sort(mesh, ctx, [uni], [pay])
    assert not fb
    assert np.array_equal(gk, np.sort(uni))
    rev = np.sort(uni)[::-1].copy()
    (gk, gp), fb = mesh_sort(mesh, ctx, [rev], [pay])
    assert fb
    assert np.array_equal(gk, np.sort(rev))

    # mutation demo: drop the global-rank tie key -> stability lost, caught
    # (moderate cardinality keeps the exchange path; all-equal keys would
    # fall back to the gathered sort, which is stable regardless)
    key = rng.integers(0, 32, 256 * 8).astype(np.int32)
    pay = np.arange(256 * 8, dtype=np.int32)
    (gk, gp), fb = mesh_sort(mesh, ctx, [key], [pay], _tie_rank=False)
    assert not fb
    order = np.lexsort((pay, key))
    assert not np.array_equal(gp, pay[order]), "tie-rank mutation not caught"
    (gk, gp), _ = mesh_sort(mesh, ctx, [key], [pay])
    assert np.array_equal(gp, pay[order])

    # boundary helpers on a real mesh
    x = jnp.asarray(rng.integers(0, 100, 64).astype(np.int32))
    def bh(v):
        vs = ctx.stripe(v)
        return (ctx.gather(ctx.edge_prev(vs, -7)),
                ctx.gather(ctx.edge_next(vs, -9)),
                ctx.gather(ctx.cumsum(vs)),
                ctx.unstripe(vs))
    f = jax.jit(jax.shard_map(bh, mesh=mesh, in_specs=(P(),),
                              out_specs=(P(), P(), P(), P()), check_vma=False))
    prev, nxt, cs, us = map(np.asarray, f(x))
    xn = np.asarray(x)
    assert np.array_equal(prev, np.concatenate([[-7], xn[:-1]]))
    assert np.array_equal(nxt, np.concatenate([xn[1:], [-9]]))
    assert np.array_equal(cs, np.cumsum(xn))
    assert np.array_equal(us, xn)
    print("DIST_SORT_OK")
""")


@pytest.mark.slow
def test_dist_sort_8dev_subprocess(tmp_path):
    script = tmp_path / "dist_sort_8dev.py"
    script.write_text(_MULTIDEV)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST_SORT_OK" in r.stdout
