"""Parameter-spec system with logical sharding axes (t5x-style, from scratch).

Every parameter is declared as a `Spec(shape, axes)` where `axes` names each
dimension with a *logical* axis ("embed", "mlp", "heads", "experts", ...).
A parallelism plan maps logical axes to mesh axes; `shardings()` resolves
them to NamedShardings with automatic divisibility fallback (a dim that
does not divide its mesh axes is replicated — e.g. 8 KV heads on a 16-way
"model" axis). Specs materialize to real arrays (smoke tests / training) or
jax.ShapeDtypeStruct stand-ins (multi-pod dry-run: no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"     # normal | zeros | ones | embed
    scale: float | None = None  # default: 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def tree_map_specs(fn: Callable[[Spec], Any], tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_spec)


def materialize(tree, rng: jax.Array, dtype=jnp.float32):
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_spec)
    rngs = jax.random.split(rng, max(len(leaves), 1))
    out = []
    for spec, r in zip(leaves, rngs):
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dtype))
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            if spec.init == "embed":
                scale = spec.scale if spec.scale is not None else 1.0
            out.append(scale * jax.random.normal(r, spec.shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstracts(tree, dtype=jnp.bfloat16):
    return tree_map_specs(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), tree)


def _resolve_pspec(spec: Spec, rules: dict[str, Any], mesh: Mesh) -> PartitionSpec:
    entries = []
    used: set[str] = set()  # a mesh axis may shard at most one dim;
    # earlier dims win (axes tuples are declared most-important-first,
    # e.g. ("experts", "embed", "mlp") keeps EP and drops the TP dim).
    for dim, name in zip(spec.shape, spec.axes):
        mapped = rules.get(name) if name else None
        if mapped is None:
            entries.append(None)
            continue
        mesh_axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        if any(ax in used for ax in mesh_axes):
            entries.append(None)
            continue
        total = 1
        for ax in mesh_axes:
            total *= mesh.shape[ax]
        if dim % total != 0:
            entries.append(None)  # divisibility fallback: replicate
        else:
            used.update(mesh_axes)
            entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def pspecs(tree, rules: dict[str, Any], mesh: Mesh):
    return tree_map_specs(lambda s: _resolve_pspec(s, rules, mesh), tree)


def shardings(tree, rules: dict[str, Any], mesh: Mesh):
    return tree_map_specs(
        lambda s: NamedSharding(mesh, _resolve_pspec(s, rules, mesh)), tree)


def param_count(tree) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(tree, is_leaf=is_spec))


def stack_specs(tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked leading dim (scan-over-layers axis) to every spec."""
    return tree_map_specs(
        lambda s: Spec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale),
        tree)


# ---------------------------------------------------------------------------
# numeric primitives
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma


def layer_norm(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma
            + beta)


def rope_freqs(head_dim: int, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: [..., S, H, D] (D even), positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [D/2]
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def softmax_cross_entropy(logits, labels, z_loss: float = 0.0):
    """Mean token CE; logits [..., V] f32-upcast; labels int32 (-1 = pad).

    The label log-prob uses a one-hot mask-and-reduce rather than
    take_along_axis: under a vocab-sharded logits layout the reduction
    lowers to a cheap all-reduce instead of an all-gather of the logits.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    v = logits.shape[-1]
    onehot = labels[..., None].clip(0) == jnp.arange(v, dtype=labels.dtype)
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    mask = labels >= 0
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1)
