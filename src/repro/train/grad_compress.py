"""Gradient compression for the data-parallel all-reduce, with error
feedback — a distributed-optimization trick for bandwidth-bound multi-pod
training (the cross-pod DCN axis is the slow link).

The DP gradient sync normally rides implicitly on XLA's SPMD partitioner
(psum of bf16/f32 grads). This module provides an explicit shard_map
alternative: grads are quantized shard-locally to int8 with a per-tensor
scale, all-reduced in low precision, dequantized, and the quantization
residual is carried as error-feedback state so the compression bias
vanishes over steps (1-bit-Adam-style convergence behaviour).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_grads(grads, residuals, mesh, axes=("data",)):
    """All-reduce `grads` over `axes` in int8 with error feedback.

    grads/residuals: pytrees of replicated-over-`axes`... in SPMD practice
    the per-shard grads live inside shard_map; here we expose the functional
    core so both the shard_map path and unit tests share it.
    Returns (synced_grads, new_residuals).
    """
    def one(g, r):
        g32 = g.astype(jnp.float32) + r
        q, scale = quantize_int8(g32)
        deq = dequantize_int8(q, scale)
        new_r = g32 - deq
        return deq, new_r

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    deq = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    res = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return deq, res


def make_compressed_allreduce(mesh, axis: str = "data"):
    """shard_map all-reduce: int8 quantize -> psum -> dequantize.

    Applied to a pytree of per-rank partial gradients (batch-sharded loss
    terms). Error feedback state is threaded by the caller.
    """
    def sync(grads, residuals):
        def local(g_tree, r_tree):
            def one(g, r):
                g32 = g.astype(jnp.float32) + r
                q, scale = quantize_int8(g32)
                qsum = jax.lax.psum(q.astype(jnp.int32), axis)
                ssum = jax.lax.psum(scale, axis)  # conservative shared scale
                n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
                deq = qsum.astype(jnp.float32) * (ssum / n)
                new_r = g32 - dequantize_int8(q, scale)
                return deq / n, new_r
            flat_g, treedef = jax.tree_util.tree_flatten(g_tree)
            flat_r = treedef.flatten_up_to(r_tree)
            outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
            return (jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs]),
                    jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs]))

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(grads, residuals)

    return sync
