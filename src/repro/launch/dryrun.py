"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline terms from the compiled artifact.

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, OOM-at-compile or unsupported collective
fails here. Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b \
      --shape train_4k [--mesh single,multi] [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import argparse
import json
import os
import re
import sys
import time

import jax
import numpy as np

# Per-chip peaks keyed by jax's ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s ICI per chip (4 links, so 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1}
_COLL_OPS = {
    "all-reduce": 2.0,          # ring: 2 (n-1)/n x bytes
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> dict:
    """Per-chip collective traffic from the post-SPMD optimized HLO.

    Shapes in the partitioned module are already per-device; we sum output
    bytes per op with a ring-cost multiplier for all-reduce."""
    totals = {k: 0.0 for k in _COLL_OPS}
    counts = {k: 0 for k in _COLL_OPS}
    for line in hlo.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1]
        for op, mult in _COLL_OPS.items():
            tok = f" {op}("
            idx = rhs.find(tok)
            if idx < 0:
                # fusion-wrapped or start-done pairs: match "-start("
                tok = f" {op}-start("
                idx = rhs.find(tok)
                if idx < 0:
                    continue
            head = rhs[:idx]
            b = sum(_shape_bytes(m.group(1), m.group(2))
                    for m in _SHAPE_RE.finditer(head))
            totals[op] += mult * b
            counts[op] += 1
            break
    return dict(bytes_by_op=totals, counts=counts,
                total_bytes=float(sum(totals.values())))


def count_params(pspecs, cfg) -> tuple[int, int]:
    """(total, active) parameter counts; MoE expert tensors scale by
    top_k/n_experts for the active count."""
    from repro.models.common import is_spec
    total = active = 0
    for path, spec in jax.tree_util.tree_flatten_with_path(
            pspecs, is_leaf=is_spec)[0]:
        n = int(np.prod(spec.shape))
        total += n
        if cfg.moe and "experts" in (spec.axes or ()):
            active += n * cfg.moe.top_k // cfg.moe.n_experts
        else:
            active += n
    return total, active


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D for prefill,
    2*N_active*B for one decode step."""
    from repro.configs.registry import SHAPES
    from repro.models import transformer as T
    sh = SHAPES[shape_name]
    _, active = count_params(T.lm_shapes(cfg), cfg)
    if sh["kind"] == "train":
        return 6.0 * active * sh["global_batch"] * sh["seq_len"]
    if sh["kind"] == "prefill":
        return 2.0 * active * sh["global_batch"] * sh["seq_len"]
    return 2.0 * active * sh["global_batch"]


def run_cell(arch: str, shape: str, multi_pod: bool,
             fsdp: bool = True, seq_shard_kv: bool = True,
             donate: bool = True, moe_local: bool = False,
             seq_parallel_attn: bool = False,
             attn_p_bf16: bool = False, mla_flash: bool = False,
             q_chunk: int = 0, k_chunk: int = 0) -> dict:
    from repro.configs import registry
    from repro.configs.registry import cell_is_runnable, get_config
    from repro.dist.sharding import Plan
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh

    if q_chunk or k_chunk:
        base = registry.CONFIGS[arch]
        registry.CONFIGS[arch] = base.scaled(
            q_chunk=q_chunk or base.q_chunk, k_chunk=k_chunk or base.k_chunk)

    ok, why = cell_is_runnable(arch, shape)
    if not ok:
        return dict(arch=arch, shape=shape,
                    mesh="multi" if multi_pod else "single",
                    status="skipped", reason=why)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = Plan.make(mesh, fsdp=fsdp, seq_shard_kv=seq_shard_kv,
                     moe_local=moe_local,
                     seq_parallel_attn=seq_parallel_attn,
                     attn_p_bf16=attn_p_bf16, mla_flash=mla_flash)
    spec = steps.input_specs(arch, shape, plan)
    fn = steps.make_step(arch, shape, plan)
    n_chips = int(np.prod(list(mesh.shape.values())))

    donate_args = ()
    if donate and spec["kind"] in ("train",):
        donate_args = (0,)
    elif donate and spec["kind"] == "decode":
        donate_args = (3,)
    jitted = jax.jit(fn, in_shardings=spec["in_shardings"],
                     donate_argnums=donate_args)
    with mesh:
        lowered = jitted.lower(*spec["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0) or 0)
    except Exception as e:  # CPU backend may not expose it
        mem["error"] = str(e)

    # trip-count-corrected per-chip costs from the optimized HLO (XLA's
    # cost_analysis visits scan bodies once; see launch/hlo_cost.py)
    from repro.launch import hlo_cost
    walked = hlo_cost.analyze(compiled.as_text())
    coll = dict(total_bytes=walked["collective_bytes"],
                counts=walked["collective_counts"],
                bytes_by_op=walked["collective_bytes_by_op"])

    flops_total = float(walked["flops"])
    bytes_total = float(walked["bytes"])
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    cfg = get_config(arch)
    mf = model_flops(cfg, shape)
    pk = PEAKS["TPU v5 lite"]   # the production meshes are v5e pods
    compute_s = flops_total / pk["flops"]
    memory_s = bytes_total / pk["hbm_bw"]
    coll_s = coll["total_bytes"] / pk["ici_bw"]
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", coll_s), key=lambda kv: kv[1])[0]
    return dict(
        arch=arch, shape=shape, mesh="multi" if multi_pod else "single",
        status="ok", n_chips=n_chips,
        t_lower_s=round(t_lower, 1), t_compile_s=round(t_compile, 1),
        hlo_flops=flops_total, hlo_bytes=bytes_total,
        xla_cost_flops=raw_flops, xla_cost_bytes=raw_bytes,
        collective_bytes=coll["total_bytes"],
        collective_counts=coll["counts"],
        collective_bytes_by_op=coll["bytes_by_op"],
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        dominant=dominant,
        model_flops=mf, model_flops_per_chip=mf / n_chips,
        useful_flop_ratio=(mf / n_chips) / flops_total if flops_total else 0,
        memory=mem,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--json", default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-seq-shard-kv", action="store_true")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--moe-local", action="store_true",
                    help="hillclimb D1: rank-local MoE dispatch")
    ap.add_argument("--sp-attn", action="store_true",
                    help="hillclimb Q1: sequence-parallel attention")
    ap.add_argument("--p-bf16", action="store_true",
                    help="hillclimb M1: bf16 PV probabilities")
    ap.add_argument("--mla-flash", action="store_true",
                    help="hillclimb D2: chunked latent MLA attention")
    ap.add_argument("--q-chunk", type=int, default=0)
    ap.add_argument("--k-chunk", type=int, default=0)
    args = ap.parse_args(argv)
    # the production meshes need 512 devices: forced host devices, set
    # before the first backend use in this process
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    from repro.configs.registry import SHAPES, list_configs
    if args.list:
        for a in list_configs():
            for s in SHAPES:
                print(f"{a} {s}")
        return 0

    results = []
    for mesh_kind in args.mesh.split(","):
        r = run_cell(args.arch, args.shape, multi_pod=(mesh_kind == "multi"),
                     fsdp=not args.no_fsdp,
                     seq_shard_kv=not args.no_seq_shard_kv,
                     donate=not args.no_donate, moe_local=args.moe_local,
                     seq_parallel_attn=args.sp_attn,
                     attn_p_bf16=args.p_bf16, mla_flash=args.mla_flash,
                     q_chunk=args.q_chunk, k_chunk=args.k_chunk)
        results.append(r)
        if r["status"] == "ok":
            print(f"[{r['mesh']}] {args.arch} x {args.shape}: "
                  f"compile {r['t_compile_s']}s | "
                  f"compute {r['compute_s']*1e3:.2f}ms "
                  f"memory {r['memory_s']*1e3:.2f}ms "
                  f"collective {r['collective_s']*1e3:.2f}ms "
                  f"-> {r['dominant']}-bound | "
                  f"useful-flop ratio {r['useful_flop_ratio']:.2f}")
            print("  memory_analysis:", r["memory"])
            print("  collectives:", r["collective_counts"])
        else:
            print(f"[{r['mesh']}] {args.arch} x {args.shape}: SKIP "
                  f"({r['reason']})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
