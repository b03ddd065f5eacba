"""The check of the program's three Pallas kernels at the solve's tile
shapes: seeded inputs (``inputs``), one call of each kernel on the chip
(``program_outputs``), and the largest gap to the plain references of
``reference.py`` (``errors``). Weights are whole numbers from 1 to 255, so
the float32 sums of a sound kernel are exact (every limit is 0) while
bfloat16 sums of them are not.

The benchmark reaches each kernel through the program's module for it (its
tile bounds and its ``*_pallas`` entry). A kernel that the program no
longer has there, or no longer takes these arguments, is left out of the
check with a line on standard error, and the others are still checked:
every solve's audit still covers the path that replaced it.
"""
from __future__ import annotations

import sys

import numpy as np

import reference

IDS = 16  # pair_scores' id range: each neighbour slot matches many lanes
NAMES = ("pair_scores", "gains", "pins_count")
# what a kernel the program has renamed, moved or retiled raises here
GONE = (ImportError, AttributeError, TypeError)


def _pair_scores_inputs(caps, kcap, rng) -> dict:
    """[N, U] neighbour slots (pad -1) against [N, L] lanes."""
    from repro.kernels.pair_scores import ops
    u, lanes = ops.tile_bounds(caps)
    n = ops.stripe_rows(caps, 1)
    nbr = rng.integers(0, IDS, (n, u), dtype=np.int32)
    nbr = np.where(np.arange(u) < rng.integers(1, u + 1, (n, 1)), nbr,
                   int(ops.NBR_PAD)).astype(np.int32)
    trav_m = rng.integers(0, IDS, (n, lanes), dtype=np.int32)
    trav_m = np.where(np.arange(lanes) < rng.integers(1, lanes + 1, (n, 1)),
                      trav_m, int(ops.TRAV_PAD)).astype(np.int32)
    trav_w = rng.integers(1, 256, (n, lanes)).astype(np.float32)
    trav_d = rng.integers(0, 2, (n, lanes), dtype=np.int32)
    return dict(nbr=nbr, trav_m=trav_m, trav_w=trav_w, trav_d=trav_d,
                lc=min(128, lanes))


def _gains_inputs(caps, kcap, rng) -> dict:
    """[N, H] incidence rows over an [E, kcap] pins-nonzero matrix."""
    from repro.kernels.gains import ops
    h = ops.h_bound(caps)
    n = ops.stripe_rows(caps, 1)
    inc = rng.integers(0, caps.e, n * h, dtype=np.int32)
    deg = rng.integers(0, h + 1, (n, 1))
    w = np.where(np.arange(h) < deg, rng.integers(1, 256, (n, h)),
                 0).astype(np.float32)
    pins_nz = (rng.random((caps.e, kcap)) < 0.7).astype(np.float32)
    return dict(inc=inc, w=w, pins_nz=pins_nz, h=h)


def _pins_count_inputs(caps, kcap, rng) -> dict:
    """[E, dbar] partition ids (>= kdim is padding)."""
    from repro.kernels.pins_count import ops
    dbar, dc = ops.tile_bounds(caps)
    e = ops.stripe_rows(caps, 1)
    kdim = max(kcap, 8)
    parts = rng.integers(0, kdim + 8, (e, dbar), dtype=np.int32)
    dst = rng.integers(0, 2, (e, dbar), dtype=np.int32)
    return dict(parts=parts, dst=dst, kdim=kdim, dc=dc)


def _pair_scores_call(x, interpret) -> dict:
    from repro.kernels.pair_scores.kernel import pair_scores_pallas
    eta, inter = pair_scores_pallas(x["nbr"], x["trav_m"], x["trav_w"],
                                    x["trav_d"], tn=8, lc=x["lc"],
                                    interpret=interpret)
    return dict(eta=eta, inter=inter)


def _gains_call(x, interpret) -> dict:
    from repro.kernels.gains.kernel import gains_pallas
    return dict(conn=gains_pallas(x["inc"], x["w"], x["pins_nz"], h=x["h"],
                                  interpret=interpret))


def _pins_count_call(x, interpret) -> dict:
    from repro.kernels.pins_count.kernel import pins_count_pallas
    pins, pins_in = pins_count_pallas(x["parts"], x["dst"], x["kdim"], te=8,
                                      dc=x["dc"], interpret=interpret)
    return dict(pins=pins, pins_in=pins_in)


_INPUTS = dict(pair_scores=_pair_scores_inputs, gains=_gains_inputs,
               pins_count=_pins_count_inputs)
_CALLS = dict(pair_scores=_pair_scores_call, gains=_gains_call,
              pins_count=_pins_count_call)


def _gone(name: str, err: Exception) -> None:
    print(f"kernel check: {name} left out, the program no longer has it "
          f"as the benchmark calls it ({type(err).__name__}: {err})",
          file=sys.stderr, flush=True)


def inputs(caps, kcap: int, seed: int) -> dict:
    """Seeded inputs of each kernel the program has, at the tile shapes it
    derives from ``caps`` and ``kcap`` (the solve's own)."""
    x = {}
    for i, name in enumerate(NAMES):
        try:
            x[name] = _INPUTS[name](caps, kcap,
                                    np.random.default_rng([seed, 12, i]))
        except GONE as err:
            _gone(name, err)
    return x


def program_outputs(x: dict) -> dict:
    """One call of each of the program's kernels in ``x``, compiled for the
    device JAX runs on, as numpy."""
    import jax
    from repro.kernels import pallas_interpret
    interp = pallas_interpret()
    out = {}
    for name, xi in x.items():
        try:
            res = jax.device_get(_CALLS[name](xi, interp))
        except GONE as err:
            _gone(name, err)
            continue
        out[name] = {k: np.asarray(v) for k, v in res.items()}
    return out


def reference_outputs(x: dict, dtype=np.float64) -> dict:
    out = {}
    if "pair_scores" in x:
        ps = x["pair_scores"]
        eta, inter = reference.pair_scores(ps["nbr"], ps["trav_m"],
                                           ps["trav_w"], ps["trav_d"],
                                           dtype=dtype)
        out["pair_scores"] = dict(eta=eta, inter=inter)
    if "gains" in x:
        gn = x["gains"]
        out["gains"] = dict(conn=reference.gains(gn["inc"], gn["w"],
                                                 gn["pins_nz"], gn["h"],
                                                 dtype=dtype))
    if "pins_count" in x:
        pc = x["pins_count"]
        pins, pins_in = reference.pins_count(pc["parts"], pc["dst"],
                                             pc["kdim"])
        out["pins_count"] = dict(pins=pins, pins_in=pins_in)
    return out


def errors(x: dict, out: dict) -> dict:
    """``<kernel>_err``: the largest absolute gap of the outputs of each
    kernel that ran to the float64 reference (a missing or misshapen
    output reads infinite)."""
    ref = reference_outputs(x)

    def gap(name):
        worst = 0.0
        for k, r in ref[name].items():
            a = np.asarray(out.get(name, {}).get(k, np.zeros(0)))
            if a.shape != r.shape:
                return float("inf")
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - r.astype(np.float64)), initial=0.0)))
        return worst

    return {f"{name}_err": gap(name) for name in ref if name in out}
