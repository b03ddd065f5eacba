"""Distributed sample sort vs gathered `lax.sort` wall-time per device
count (the communication pattern the PR 4 refactor replaced, measured in
isolation from the V-cycle).

Each device count runs in a fresh subprocess (XLA device topology fixes at
backend init) on a (1, n)-mesh. Both sides sort the same three-int-key +
payload columns under `shard_map`: the distributed side through
`ShardCtx.sort_by` (stripes in / stripes out — splitter samples are the
only gathered keys), the baseline through the legacy gather -> replicated
`lax.sort` -> stripe pattern. Second run timed (first pays compile). On
this CPU container the "devices" are host threads, so the columns chart
overhead/scaling shape; on a real mesh the same harness measures actual
traffic savings. `fell_back` counts capacity-overflow fallbacks (0 on this
workload).

  PYTHONPATH=src python -m benchmarks.dist_sort
  PYTHONPATH=src python -m benchmarks.run --only dist_sort
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

DEVICE_COUNTS = (1, 2, 4, 8)
N_PER_SHARD = 1 << 15

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + sys.argv[1])
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.utils import segops

    n_dev = int(sys.argv[1])
    n = int(sys.argv[2]) * n_dev
    mesh = jax.make_mesh((n_dev,), ("model",))
    ctx = segops.ShardCtx(axis="model", nshards=n_dev)
    rng = np.random.default_rng(0)
    cols = [jnp.asarray(rng.integers(0, hi, n).astype(np.int32))
            for hi in (1 << 20, 1 << 10, 1 << 4)]
    pay = jnp.arange(n, dtype=jnp.int32)

    def dist_body(a, b, c, p):
        ks = [ctx.stripe(x) for x in (a, b, c)]
        from repro.dist import sort as dist_sort
        ko, po, fb = dist_sort.sample_sort_stripes(
            ctx, ks, [ctx.stripe(p)], with_stats=True)
        return (*ko, *po, fb)

    def gath_body(a, b, c, p):
        ks = [ctx.gather(ctx.stripe(x)) for x in (a, b, c)]
        (s1, s2, s3), (sp,) = segops.sort_by(ks, [ctx.gather(ctx.stripe(p))])
        return (ctx.stripe(s1), ctx.stripe(s2), ctx.stripe(s3),
                ctx.stripe(sp), jnp.asarray(False))

    out = {}
    for name, body in (("dist", dist_body), ("gather", gath_body)):
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),) * 4,
            out_specs=(P("model"),) * 4 + (P(),), check_vma=False))
        r = f(*cols, pay)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = f(*cols, pay)
        jax.block_until_ready(r)
        out[name + "_s"] = time.perf_counter() - t0
        out[name + "_fell_back"] = bool(np.asarray(r[-1]).reshape(-1)[0])
    out["n"] = n
    print(json.dumps(out))
""")


def run() -> list[str]:
    from benchmarks.common import row

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    # forced host devices: the children never open an accelerator
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = []
    for n in DEVICE_COUNTS:
        try:
            r = subprocess.run(
                [sys.executable, "-c", _CHILD, str(n), str(N_PER_SHARD)],
                env=env, capture_output=True, text=True, timeout=1800)
        except subprocess.TimeoutExpired:
            out.append(row(f"dist_sort/dev{n}", 0.0, "ERROR: timeout"))
            continue
        if r.returncode != 0:
            err = (r.stderr.strip().splitlines() or ["no stderr"])[-1]
            out.append(row(f"dist_sort/dev{n}", 0.0, f"ERROR: {err[:120]}"))
            continue
        m = json.loads(r.stdout.strip().splitlines()[-1])
        out.append(row(
            f"dist_sort/dev{n}", m["dist_s"] * 1e6,
            f"dist_s={m['dist_s']:.4f} gather_s={m['gather_s']:.4f} "
            f"rel_gather={m['dist_s'] / max(m['gather_s'], 1e-9):.2f}x "
            f"n={m['n']} fell_back={int(m['dist_fell_back'])}"))
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)
