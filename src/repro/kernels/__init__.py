"""Pallas TPU kernels for the partitioner's compute hot spots.

The paper's dominant kernels (Fig. 8) are the two neighborhood traversals:
candidate-pairs proposal (coarsening) and refinement gain calculation, plus
the pins(p,e) matrix precomputation that feeds the latter. Each kernel here
is the TPU-native redesign of the corresponding CUDA kernel:

  pins_count  — shared-memory atomic counters      -> one-hot compare+reduce
                over VMEM tiles, grid-accumulated across cardinality chunks.
  pair_scores — warp shared-memory histogram with
                per-pin binary search (Fig. 3)      -> dense equality-matmul:
                eta[t,u] = sum_l w[t,l] * (trav[t,l] == nbr[t,u]), with the
                inter() counter accumulated from a dst-flag plane in the
                same pass (the paper's in-histogram constraint tracking).
  gains       — warp-per-node gain loops over the
                pins matrix                         -> DMA gather: each
                8-node tile reads its incidence rows from SMEM and DMAs the
                pins-matrix row of every live incident edge from HBM.
  flash_attn  — framework-side hot spot: online-softmax attention with
                the score block and running max/denominator resident in
                VMEM, grid-accumulated over key chunks; HBM traffic
                collapses to q/k/v/o.

Each subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper + padding/layout glue) and ref.py (pure-jnp oracle). Tests sweep
shapes against the oracles in interpret mode on CPU, and
``tests/test_tpu_compile.py`` compiles the partitioner kernels for a v5e
at full-size tile shapes.

Dispatch contract
-----------------

The kernels are *routed*, never called unconditionally. With
``use_kernels=True`` the hot-loop call sites (``coarsen.propose`` for
``pair_scores``, ``refine.propose_moves`` for ``gains``,
``refine.refine_step_impl`` for ``pins_count``) dispatch through a runtime
``fits_kernel`` predicate under ``lax.cond``:

* **kernel branch** — taken when every node's live extent fits the static
  tile bounds (``tile_bounds`` / ``h_bound``, derived from the level-0
  ``Caps`` statistics, clamped by the capacity caps). Tile bounds are not
  monotone under coarsening (merged nodes union their neighborhoods), so
  coarse levels may legitimately outgrow them.
* **fallback branch** — the pure-XLA segment pipeline
  (``coarsen.score_slots`` / the ``_conn_segments`` closure), bit-identical
  to the ``use_kernels=False`` path. Falling back is silent at the
  arithmetic level but *not* at the accounting level: every dispatch
  reports a ``kernel_path_taken`` flag (the cond predicate as int32),
  aggregated per level into ``PartitionResult.kernel_path`` so tests and
  benchmarks assert coverage instead of trusting the routing.

Sharded mode: the ``pair_scores`` and ``gains`` wrappers accept a
``segops.ShardCtx`` and then run *stripe-locally* under ``shard_map`` —
each shard builds dense tiles only for its contiguous row stripe of the
node axis, runs the kernel on its tile, and the row stripes concatenate in
shard order (``ctx.gather`` — disjoint rows, so the combine is exact for
floats and ints alike). Per-row kernel arithmetic is independent of the
tile height and identical across mesh shapes, so the sharded kernel output
is bit-identical to the single-device kernel output. The ``fits_kernel``
predicates combine per-stripe counts with integer psums and use the *same*
static bounds on every mesh shape, so the cond branch taken at a given
level is mesh-independent — the invariant the ``race=False`` bit-exact
parity contract of ``dist.partition`` relies on.

Interpret policy
----------------

``pallas_interpret()`` below decides compiled-vs-interpret per trace from
the backend alone: compiled on TPU, interpret on CPU (which has no
compiled Pallas path). Any other backend is an error: the kernels are
written for the TPU, and nothing here may run them elsewhere unnoticed.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Whether pallas_call runs in interpret mode for this process: False
    on TPU, True on CPU. Raises on any other backend. Evaluated at trace
    time."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} is neither")
