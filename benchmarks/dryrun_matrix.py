"""Drive the full (arch x shape x mesh) dry-run matrix as subprocesses.

Each cell runs in its own process (fresh XLA state, bounded memory) and
writes results/dryrun/<arch>__<shape>.json containing both mesh passes.

  PYTHONPATH=src python benchmarks/dryrun_matrix.py [--only arch:shape,...]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "results", "dryrun")

ARCHS = ["qwen2-1.5b", "minitron-8b", "phi4-mini-3.8b", "yi-34b",
         "xlstm-350m", "llama4-scout-17b-16e", "deepseek-v2-236b",
         "whisper-tiny", "internvl2-2b", "jamba-v0.1-52b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

# run a representative sample first so analysis can start early
PRIORITY = [
    ("qwen2-1.5b", "train_4k"), ("deepseek-v2-236b", "train_4k"),
    ("yi-34b", "decode_32k"), ("jamba-v0.1-52b", "train_4k"),
    ("minitron-8b", "prefill_32k"), ("xlstm-350m", "long_500k"),
]


def cells():
    seen = set()
    for c in PRIORITY:
        seen.add(c)
        yield c
    for a in ARCHS:
        for s in SHAPES:
            if (a, s) not in seen:
                yield (a, s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)

    todo = list(cells())
    if args.only:
        want = set(tuple(x.split(":")) for x in args.only.split(","))
        todo = [c for c in todo if c in want]

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # the dry run compiles on forced host devices, never on an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    t00 = time.time()
    for i, (arch, shape) in enumerate(todo):
        out_json = os.path.join(OUT, f"{arch}__{shape}.json")
        if os.path.exists(out_json) and not args.force:
            print(f"[{i+1}/{len(todo)}] {arch} x {shape}: cached", flush=True)
            continue
        t0 = time.time()
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", args.mesh, "--json", out_json]
        try:
            r = subprocess.run(cmd, env=env, timeout=args.timeout,
                               capture_output=True, text=True)
            status = "ok" if r.returncode == 0 else f"rc={r.returncode}"
            if r.returncode != 0:
                with open(out_json + ".err", "w") as f:
                    f.write(r.stdout[-4000:] + "\n====\n" + r.stderr[-8000:])
        except subprocess.TimeoutExpired:
            status = "timeout"
        print(f"[{i+1}/{len(todo)}] {arch} x {shape}: {status} "
              f"({time.time()-t0:.0f}s, total {time.time()-t00:.0f}s)",
              flush=True)
    print("matrix done in %.0fs" % (time.time() - t00))


if __name__ == "__main__":
    main()
