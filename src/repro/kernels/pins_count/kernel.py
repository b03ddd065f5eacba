"""pins(p, e) matrix kernel: per-edge partition pin counts.

CUDA original (paper Sec. VI-B): a warp per h-edge, one shared-memory
counter per partition, threads atomically increment counters after mapping
each pin through rho. TPU redesign: no atomics/scratchpad scatter — instead
a one-hot compare+reduce over VMEM tiles. The grid walks (edge tiles x
cardinality chunks); the output block for an edge tile is revisited across
the cardinality chunks (TPU grids iterate sequentially), accumulating in
place, so arbitrarily large cardinalities stream through a fixed VMEM
working set. A middle grid axis walks the partition ids in chunks of KC,
so the working set does not grow with the partition count either:

  grid  = (E/TE, K/KC, dbar/DC)
  parts = int32[E, dbar]   partition id per (edge, pin slot), K = padding
  out   = int32[E, K]      pins / pins_in counts

Block shapes: parts (TE, DC), out (TE, KC); the VMEM working set is the
one-hot compare tile (TE, DC, KC). KC = K when K <= KC_MAX; larger K is
padded to a multiple of KC_MAX (padding columns count nothing and are
sliced off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


KC_MAX = 512


def _pins_kernel(parts_ref, dst_ref, pins_ref, pins_in_ref, *, kc: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        pins_ref[...] = jnp.zeros_like(pins_ref)
        pins_in_ref[...] = jnp.zeros_like(pins_in_ref)

    parts = parts_ref[...]                       # [TE, DC] int32
    dst = dst_ref[...]                           # [TE, DC] int32 (0/1)
    iota_k = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, kc), 2)
              + pl.program_id(1) * kc)
    onehot = (parts[:, :, None] == iota_k).astype(jnp.int32)   # [TE, DC, K]
    pins_ref[...] += jnp.sum(onehot, axis=1)
    pins_in_ref[...] += jnp.sum(onehot * dst[:, :, None], axis=1)


@functools.partial(jax.jit, static_argnames=("kdim", "te", "dc", "interpret"))
def pins_count_pallas(parts_dense: jax.Array, dst_dense: jax.Array,
                      kdim: int, te: int = 8, dc: int = 128,
                      interpret: bool = True):
    """parts_dense/dst_dense: [E, dbar] (padding lanes must carry part id >=
    kdim so the one-hot drops them). Returns (pins, pins_in): [E, kdim]."""
    e, dbar = parts_dense.shape
    assert e % te == 0 and dbar % dc == 0, (e, dbar, te, dc)
    kc = min(kdim, KC_MAX)
    kp = -(-kdim // kc) * kc
    if kp != kdim:
        # padding ids in [kdim, kp) would be counted: move them past kp
        parts_dense = jnp.where(parts_dense < kdim, parts_dense, kp)
    out_shape = [jax.ShapeDtypeStruct((e, kp), jnp.int32)] * 2
    pins, pins_in = pl.pallas_call(
        functools.partial(_pins_kernel, kc=kc),
        grid=(e // te, kp // kc, dbar // dc),
        in_specs=[
            pl.BlockSpec((te, dc), lambda i, c, j: (i, j)),
            pl.BlockSpec((te, dc), lambda i, c, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((te, kc), lambda i, c, j: (i, c)),
            pl.BlockSpec((te, kc), lambda i, c, j: (i, c)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(parts_dense, dst_dense.astype(jnp.int32))
    return pins[:, :kdim], pins_in[:, :kdim]
