"""Refinement gain kernel: conn_w[n, p] = sum_{e in I(n)} w(e)*[pins(p,e)>0].

CUDA original (Sec. VI-B): a warp per node allocates one gain variable per
partition in shared memory and streams the node's incident h-edges, reading
pins(p, e) columns. TPU redesign: the irregular gather of pins rows is a
manual DMA gather. Each grid step owns a tile of ``TN = 8`` nodes; their
incidence rows (edge ids and weights) arrive as an (8, H) SMEM block, and
for every live slot (w != 0) the kernel DMAs the pins row of edge
``inc[n, j]`` from HBM into a VMEM staging buffer, then accumulates
``conn[n] += w[n, j] * row`` in slot order (span = h, as the paper's
warp-sequential incident-edge loop), with the partition axis across lanes.

  grid     = (N/TN, Kp/KC)                (node tiles x partition chunks)
  inc      : int32[N, H]   SMEM block (TN, H)   (pad slots: any id, w = 0)
  w        : f32[N, H]     SMEM block (TN, H)
  pins_nz  : f32[E, 1, Kp] HBM (pl.ANY), row e DMA'd per live slot
  conn     : f32[N, Kp]    block (TN, KC)
  buf      : f32[TN*HC, 1, KC] VMEM staging for one chunk of HC slots

Slots with w == 0 are skipped: adding the exact +0.0 they contribute is the
identity, so skipping keeps every row bit-identical to the plain
slot-order sum, independently of the tile height (the stripe-local contract
in ``repro.kernels``). The row-of-one ``[E, 1, Kp]`` layout makes a single
pins row a legal DMA slice (a 1-row slice of a 2-D (8, 128)-tiled array is
not). ``Kp`` pads K with zero columns to a multiple of KC, itself a
multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TN = 8          # node rows per grid step
HC_MAX = 64     # incidence slots staged per DMA round
KC_MAX = 2048   # partition lanes per grid step


def _gains_kernel(inc_ref, w_ref, pins_hbm, conn_ref, buf, sem, *,
                  h: int, hc: int, kc: int):
    col = pl.ds(pl.multiple_of(pl.program_id(1) * kc, kc), kc)
    conn_ref[...] = jnp.zeros_like(conn_ref)

    def copy(r, j, lo):
        return pltpu.make_async_copy(
            pins_hbm.at[pl.ds(inc_ref[r, j], 1), :, col],
            buf.at[pl.ds((j - lo) * TN + r, 1)], sem.at[0])

    def each_live(lo, hi, fn):
        for r in range(TN):
            def body(j, carry, r=r):
                @pl.when(w_ref[r, j] != 0.0)
                def _():
                    fn(r, j)
                return carry
            jax.lax.fori_loop(lo, hi, body, 0)

    for lo in range(0, h, hc):
        hi = min(h, lo + hc)
        each_live(lo, hi, lambda r, j: copy(r, j, lo).start())
        # one shared semaphore: every copy of the round must land before
        # any staged row is read
        each_live(lo, hi, lambda r, j: copy(r, j, lo).wait())

        def acc(r, j):
            row = buf[pl.ds((j - lo) * TN + r, 1), 0, :]
            conn_ref[pl.ds(r, 1), :] += w_ref[r, j] * row
        each_live(lo, hi, acc)


@functools.partial(jax.jit, static_argnames=("h", "interpret"))
def gains_pallas(inc: jax.Array, w: jax.Array, pins_nz: jax.Array,
                 h: int, interpret: bool = True):
    """inc: [N*H] int32 edge ids (pad slots carry w = 0). w: [N, H] f32.
    pins_nz: [E, K] f32 (1.0 where pins(p,e) > 0). Returns conn [N, K].
    N must be a multiple of ``TN``."""
    n = w.shape[0]
    e, k = pins_nz.shape
    assert n % TN == 0, (n, TN)
    # partition lanes in whole 128-lane tiles (a DMA slices whole tiles)
    kc = min(-(-k // 128) * 128, KC_MAX)
    kp = -(-k // kc) * kc
    if kp != k:
        pins_nz = jnp.pad(pins_nz, ((0, 0), (0, kp - k)))
    hc = min(h, HC_MAX)
    conn = pl.pallas_call(
        functools.partial(_gains_kernel, h=h, hc=hc, kc=kc),
        grid=(n // TN, kp // kc),
        in_specs=[
            pl.BlockSpec((TN, h), lambda i, c: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TN, h), lambda i, c: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TN, kc), lambda i, c: (i, c)),
        out_shape=jax.ShapeDtypeStruct((n, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((TN * hc, 1, kc), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret,
    )(inc.reshape(n, h), w, pins_nz.reshape(e, 1, kp))
    return conn[:, :k]
