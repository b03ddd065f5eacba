#!/usr/bin/env python3
"""On-chip smoke test of the constrained partitioner's main path.

  python3 chip_smoke.py                 # one TPU chip
  python3 chip_smoke.py --four-chips    # the (1, 4) mesh solve only

Default phases, all in this one process (a chip belongs to one process):

1. The backend must be a TPU; anything else exits non-zero at once.
2. A seeded small-world SNN (the paper's "-rand" topology, fanout 16) is
   partitioned onto Loihi-class cores (Omega = 1024 neurons, Delta = 4096
   distinct inbound axons per core; Davies et al., IEEE Micro 2018) by
   ``repro.core.partitioner.partition(use_kernels=True)``, twice: the first
   solve includes compilation, the second is warm. Both must pass the numpy
   audit of ``core/metrics.py``, agree with each other, and show the Pallas
   branch taken at level 0 for coarsen, refine and pins, with
   ``pallas_interpret()`` False. The graph has 4096 neurons by default
   (``--nodes`` overrides; 131072 is the SNN-scale target, see
   ``PERF.md``): its cold run took 758 s of the 1200 s budget on one v5e,
   split between compiling the level steps (about 214 s) and refinement
   on the device (about 205 s per solve). 16384 neurons did not finish
   compiling in 560 s; 8192 was not measured.
3. Each partitioner kernel runs once at the solve's tile shapes on seeded
   data and is compared with its ``ref.py`` oracle on row blocks (kernel
   rows are independent, and the full oracle of pair_scores would not fit
   the chip): integers exactly, integer-weighted gains exactly, pair_scores
   eta within atol 1e-4 / rtol 1e-5.
4. ``repro.launch.partition_service --mixed`` answers a few requests on
   both the vmapped bucket lane and the routed V-cycle lane; every reply
   must pass its audit.

``--four-chips`` runs only the mesh path: the same graph on a (1, 4)
``Plan`` with ``race=False, shard_graph=True, use_kernels=True``, checked bit
for bit against the one-chip solve made in the same process, with the graph
stripes on four devices. The check needs k > 1, so more than Omega neurons:
``--four-chips --nodes 2048`` is the smallest power of two.

Details go to earlier lines; the last line of stdout is one JSON object
``{"ok": true, "device": {...}}``. Any failed check raises, and the exit
code is then non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

OMEGA = 1024          # neurons per core
DELTA = 4096          # distinct inbound axons per core
FANOUT = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax backend "
                         f"{devs[0].platform!r}); nothing was run")
    return devs


def make_graph(n_nodes: int, seed: int):
    from repro.core import generate
    t = time.perf_counter()
    hg = generate.snn_smallworld(n_nodes=n_nodes, fanout=FANOUT, seed=seed)
    log(f"graph: snn_smallworld n={n_nodes} fanout={FANOUT} seed={seed} "
        f"built in {time.perf_counter() - t:.3f}s stats={hg.stats()}")
    return hg


def solve(hg, **kw):
    from repro.core.partitioner import partition
    t = time.perf_counter()
    res = partition(hg, omega=OMEGA, delta=DELTA, use_kernels=True, **kw)
    return res, time.perf_counter() - t


def check_result(res, tag: str) -> None:
    aud = res.audit
    log(f"{tag}: k={res.n_parts} levels={res.n_levels} "
        f"connectivity={res.connectivity} cut_net={res.cut_net} "
        f"max_size={aud.get('max_size')} max_inbound={aud.get('max_inbound')} "
        f"size_ok={aud['size_ok']} inbound_ok={aud['inbound_ok']}")
    log(f"{tag}: kernel_path={json.dumps(res.kernel_path)}")
    assert aud["size_ok"], f"{tag}: a block exceeds Omega={OMEGA}"
    assert aud["inbound_ok"], f"{tag}: a block exceeds Delta={DELTA}"
    kp = res.kernel_path
    for phase in ("coarsen", "refine", "pins"):
        assert kp[phase] and kp[phase][0] > 0, (
            f"{tag}: level 0 of {phase} did not take the Pallas branch: {kp}")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main_solve(args, dev):
    import numpy as np
    from repro.core.hypergraph import Caps
    from repro.core.partitioner import _next_pow2
    from repro.kernels import pallas_interpret

    assert pallas_interpret() is False, "Pallas would run interpreted"
    hg = make_graph(args.nodes, args.seed)
    caps = Caps.for_host(hg)
    log(f"caps: {caps}")
    res1, t1 = solve(hg)
    log(f"solve 1 (compile + run): {t1:.3f}s timings={res1.timings}")
    check_result(res1, "solve 1")
    res2, t2 = solve(hg)
    log(f"solve 2 (warm): {t2:.3f}s timings={res2.timings}")
    check_result(res2, "solve 2")
    assert np.array_equal(res1.parts, res2.parts), "solves disagree"
    # refinement's partition count is the coarsest level's node count
    k = res1.level_stats[-1].nodes
    kcap = _next_pow2(k)
    log(f"k={k} kcap={kcap} peak_bytes_in_use={peak_bytes(dev)}")
    return hg, caps, kcap


def check_kernels(caps, kcap: int, seed: int) -> None:
    """One call of each kernel at the solve's tile shapes on seeded device
    data, against its oracle on row blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import pallas_interpret
    from repro.kernels.gains import ops as g_ops
    from repro.kernels.gains.kernel import gains_pallas
    from repro.kernels.gains.ref import gains_ref
    from repro.kernels.pair_scores import ops as ps_ops
    from repro.kernels.pair_scores.kernel import pair_scores_pallas
    from repro.kernels.pair_scores.ref import pair_scores_ref
    from repro.kernels.pins_count import ops as pc_ops
    from repro.kernels.pins_count.kernel import pins_count_pallas
    from repro.kernels.pins_count.ref import pins_count_ref

    interpret = pallas_interpret()
    keys = jax.random.split(jax.random.PRNGKey(seed), 12)

    def blocks(n: int, r: int):
        return [slice(0, r), slice(n // 2 - r // 2, n // 2 + r // 2),
                slice(n - r, n)]

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        return out, time.perf_counter() - t

    # pair_scores: [N, U] unique-neighbor slots vs [N, L] traversal lanes
    U, L = ps_ops.tile_bounds(caps)
    n = ps_ops.stripe_rows(caps, 1)
    ids = 512   # small id range: equality hits are frequent
    nbr = jax.random.randint(keys[0], (n, U), 0, ids, jnp.int32)
    nbr = jnp.where(jnp.arange(U) < jax.random.randint(
        keys[1], (n, 1), 1, U + 1), nbr, ps_ops.NBR_PAD)
    tm = jax.random.randint(keys[2], (n, L), 0, ids, jnp.int32)
    tm = jnp.where(jnp.arange(L) < jax.random.randint(
        keys[3], (n, 1), 1, L + 1), tm, ps_ops.TRAV_PAD)
    tw = jax.random.uniform(keys[4], (n, L), jnp.float32)
    td = jax.random.bernoulli(keys[5], 0.5, (n, L)).astype(jnp.int32)
    fn = jax.jit(lambda *a: pair_scores_pallas(*a, tn=8, lc=min(128, L),
                                               interpret=interpret))
    (eta, inter), dt = timed(fn, nbr, tm, tw, td)
    for sl in blocks(n, 256):
        e2, i2 = pair_scores_ref(nbr[sl], tm[sl], tw[sl], td[sl])
        np.testing.assert_allclose(np.asarray(eta[sl]), np.asarray(e2),
                                   atol=1e-4, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(inter[sl]), np.asarray(i2))
    log(f"kernel pair_scores [N={n}, U={U}, L={L}]: matches oracle "
        f"(eta atol 1e-4 rtol 1e-5, inter exact); one call {dt:.6f}s")

    # gains: [N, H] incidence rows over an [E, kcap] pins-nonzero matrix;
    # integer-valued weights (the SNN's spike counts) make the sums exact
    H = g_ops.h_bound(caps)
    n = g_ops.stripe_rows(caps, 1)
    inc = jax.random.randint(keys[6], (n * H,), 0, caps.e, jnp.int32)
    deg = jax.random.randint(keys[7], (n, 1), 0, H + 1)
    w = jnp.where(jnp.arange(H) < deg, jax.random.poisson(
        keys[8], 8.0, (n, H)).astype(jnp.float32) + 1.0, 0.0)
    pnz = jax.random.bernoulli(keys[9], 0.3, (caps.e, kcap)).astype(
        jnp.float32)
    fn = jax.jit(lambda *a: gains_pallas(*a, h=H, interpret=interpret))
    conn, dt = timed(fn, inc, w, pnz)
    for sl in blocks(n, 1024):
        rows = slice(sl.start * H, sl.stop * H)
        np.testing.assert_array_equal(
            np.asarray(conn[sl]), np.asarray(gains_ref(inc[rows], w[sl],
                                                       pnz, H)))
    log(f"kernel gains [N={n}, H={H}, E={caps.e}, K={kcap}]: matches "
        f"oracle exactly; one call {dt:.6f}s")

    # pins_count: [E, dbar] partition ids (>= kdim is padding)
    dbar, dc = pc_ops.tile_bounds(caps)
    e = pc_ops.stripe_rows(caps, 1)
    kdim = max(kcap, 8)
    parts = jax.random.randint(keys[10], (e, dbar), 0, kdim + 8, jnp.int32)
    dst = jax.random.bernoulli(keys[11], 0.5, (e, dbar)).astype(jnp.int32)
    fn = jax.jit(lambda *a: pins_count_pallas(*a, kdim, te=8, dc=dc,
                                              interpret=interpret))
    (pins, pins_in), dt = timed(fn, parts, dst)
    for sl in blocks(e, 4096):
        p2, pi2 = pins_count_ref(parts[sl], dst[sl], kdim)
        np.testing.assert_array_equal(np.asarray(pins[sl]), np.asarray(p2))
        np.testing.assert_array_equal(np.asarray(pins_in[sl]),
                                      np.asarray(pi2))
    log(f"kernel pins_count [E={e}, dbar={dbar}, kdim={kdim}]: matches "
        f"oracle exactly; one call {dt:.6f}s")


def check_service() -> None:
    from repro.launch import partition_service
    t = time.perf_counter()
    # 3 requests batch in the 64-node bucket; the 4th (512 nodes, above
    # the 256-node threshold) takes the routed V-cycle
    out = partition_service.main(["--requests", "4", "--mixed",
                                  "--route-threshold", "256"])
    log(f"service: {out['requests']} requests in "
        f"{time.perf_counter() - t:.3f}s routes={out['routes']}")
    assert set(out["routes"]) == {"bucket", "vcycle"}, out["routes"]
    assert out["all_size_ok"] and out["all_inbound_ok"], out


def four_chips(args, devs):
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.coarsen import CoarsenParams
    from repro.core.hypergraph import Caps
    from repro.core.partitioner import (_next_pow2, make_coarsen_fns,
                                        make_refine_fn)
    from repro.core.refine import RefineParams
    from repro.dist.graph import sharded_from_host
    from repro.dist.sharding import Plan

    assert len(devs) >= 4, f"--four-chips needs 4 devices, has {len(devs)}"
    plan = Plan.make(jax.make_mesh((1, 4), ("data", "model"),
                                   devices=devs[:4]))
    hg = make_graph(args.nodes, args.seed)
    caps = Caps.for_host(hg)
    stripes = sharded_from_host(hg, caps, plan)
    for f in ("edge_pins", "node_edges", "node_is_in"):
        ds = getattr(stripes.g, f).sharding.device_set
        assert len(ds) == 4, f"{f} sits on {len(ds)} device(s)"
    log(f"graph stripes: edge_pins/node_edges/node_is_in on 4 devices, "
        f"{stripes.pins_bytes_per_device()} pins bytes per device")

    # The mesh solve compiles its coarsen, contract and refine steps one
    # after another, each for minutes. Calling each once on the level-0
    # stripes (no-op match, all-zero parts) fills the same jit caches in
    # parallel. kcap is guessed from the coarsening target; a wrong guess
    # only leaves the refine step to compile inside the solve.
    k = math.ceil(hg.n_nodes / OMEGA)
    kcap = _next_pow2(k)
    _coarsen, _contract = make_coarsen_fns(
        CoarsenParams(omega=OMEGA, delta=DELTA, use_kernels=True), plan)
    _refine = make_refine_fn(
        k, kcap, RefineParams(omega=OMEGA, delta=DELTA, use_kernels=True),
        None, plan, race=False, race_seed=0)
    no_match = jax.device_put(jnp.full((caps.n,), -1, jnp.int32),
                              NamedSharding(plan.mesh, P()))
    warm = [lambda: _coarsen(stripes, caps),
            lambda: _contract(stripes, no_match, caps),
            lambda: _refine(stripes, jnp.zeros((caps.n,), jnp.int32), caps,
                            0)]

    def one_chip():
        with jax.default_device(devs[0]):
            return solve(hg)

    def mesh_solve():
        t = time.perf_counter()
        for f in [pool.submit(lambda fn=fn: jax.block_until_ready(fn()))
                  for fn in warm]:
            f.result()
        log(f"(1, 4) mesh level steps compiled in parallel: "
            f"{time.perf_counter() - t:.3f}s (kcap guess {kcap})")
        return solve(hg, plan=plan, race=False, shard_graph=True)

    # the one-chip solve compiles and runs beside the mesh warm-up
    t = time.perf_counter()
    with ThreadPoolExecutor(5) as pool:
        f_one = pool.submit(one_chip)
        f_mesh = pool.submit(mesh_solve)
        (one, t1), (mesh, tm) = f_one.result(), f_mesh.result()
    del stripes
    log(f"one chip: {t1:.3f}s; (1, 4) mesh solve after warm-up: {tm:.3f}s; "
        f"all: {time.perf_counter() - t:.3f}s (concurrent)")
    check_result(one, "one chip")
    check_result(mesh, "(1, 4) mesh")
    assert np.array_equal(one.parts, mesh.parts), (
        f"mesh parts differ from one chip at "
        f"{int(np.sum(one.parts != mesh.parts))} nodes")
    log("mesh parts are bit-identical to the one-chip solve")
    return 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4) mesh solve and its one-chip "
                         "comparison")
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: {SRC}/repro not found; run from a "
                         f"checkout of the repository")
    sys.path.insert(0, SRC)
    devs = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")

    if args.four_chips:
        count = four_chips(args, devs)
    else:
        _, caps, kcap = main_solve(args, devs[0])
        check_kernels(caps, kcap, args.seed)
        check_service()
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
