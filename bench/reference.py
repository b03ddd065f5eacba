"""Plain references that decide ``correct``. Nothing here imports the
program.

* ``audit``: the partition's guarantees and quality, recomputed from the
  graph and the returned parts with vectorised numpy.
* ``gains``, ``pair_scores``, ``pins_count``: what each of the program's
  three Pallas kernels computes, in float64 / int64 numpy. ``dtype`` gives
  the precision of the sums, so the same functions serve as the control at
  a lower precision.
* ``pack_in_order``: the control partitioner, which packs nodes into blocks
  of omega in id order and ignores delta (it breaks a guarantee the
  configurations state, so it must come out as not correct).
"""
from __future__ import annotations

import numpy as np


def _pin_edge(g: dict) -> tuple[np.ndarray, np.ndarray]:
    card = np.diff(g["edge_off"])
    edge = np.repeat(np.arange(len(card)), card)
    rank = np.arange(len(g["edge_pins"])) - g["edge_off"][edge]
    return edge, rank


def audit(g: dict, parts, omega: int, delta: int) -> dict:
    """Every node on one block, block sizes, distinct inbound hyperedges
    per block (edges with a destination pin in it), connectivity
    ``sum_e w_e (lambda_e - 1)`` and the share of the total
    ``sum_e w_e (|e| - 1)`` it cuts."""
    n = g["n_nodes"]
    parts = np.asarray(parts)
    ok = (parts.shape == (n,) and np.issubdtype(parts.dtype, np.integer))
    if not ok:
        return dict(unassigned=n, n_blocks=0, max_block=0, max_inbound=0,
                    connectivity=float("nan"), cut_share=float("nan"))
    parts = parts.astype(np.int64)
    unassigned = int((parts < 0).sum())
    p = np.where(parts < 0, 0, parts)
    k = int(p.max()) + 1 if n else 0
    sizes = np.bincount(p, minlength=k)
    edge, rank = _pin_edge(g)
    n_edges = len(g["edge_nsrc"])
    pin_part = p[g["edge_pins"]]
    is_dst = rank >= g["edge_nsrc"][edge]
    inbound = np.bincount(np.unique(pin_part[is_dst] * n_edges
                                    + edge[is_dst]) // n_edges,
                          minlength=k)
    lam = np.bincount(np.unique(edge * k + pin_part) // k,
                      minlength=n_edges)
    w = g["edge_w"].astype(np.float64)
    conn = float((w * np.maximum(lam - 1, 0)).sum())
    total = float((w * np.maximum(np.diff(g["edge_off"]) - 1, 0)).sum())
    return dict(unassigned=unassigned, n_blocks=int((sizes > 0).sum()),
                max_block=int(sizes.max(initial=0)),
                max_inbound=int(inbound.max(initial=0)),
                connectivity=conn, cut_share=conn / total if total else 0.0)


def pack_in_order(g: dict, omega: int) -> np.ndarray:
    """Control partition: node i on block i // omega (delta ignored)."""
    return np.arange(g["n_nodes"], dtype=np.int64) // omega


def gains(inc, w, pins_nz, h: int, dtype=np.float64) -> np.ndarray:
    """conn[n, k] = sum_j w[n, j] * pins_nz[inc[n, j], k]; ``inc`` is
    [N * h] edge ids, ``w`` [N, h], ``pins_nz`` [E, K]."""
    w = np.asarray(w)
    n = w.shape[0]
    cols = np.asarray(pins_nz)[np.asarray(inc).reshape(n, h)]
    out = np.zeros((n, cols.shape[2]), dtype)
    for j in range(h):  # slot order, each partial sum held in ``dtype``
        out = (out + (w[:, j, None].astype(dtype)
                      * cols[:, j].astype(dtype))).astype(dtype)
    return out


def pair_scores(nbr, trav_m, trav_w, trav_d, dtype=np.float64,
                rows: int = 32):
    """eta[n, u] = sum_l [trav_m[n, l] == nbr[n, u]] * trav_w[n, l] and
    inter[n, u] = sum_l [trav_m[n, l] == nbr[n, u]] * [trav_d[n, l] != 0],
    in blocks of ``rows`` rows."""
    nbr, trav_m = np.asarray(nbr), np.asarray(trav_m)
    trav_w, trav_d = np.asarray(trav_w), np.asarray(trav_d)
    n, u = nbr.shape
    eta = np.zeros((n, u), dtype)
    inter = np.zeros((n, u), np.int64)
    for r in range(0, n, rows):
        sl = slice(r, r + rows)
        eq = trav_m[sl, :, None] == nbr[sl, None, :]
        for lane in range(trav_m.shape[1]):
            eta[sl] = (eta[sl] + (eq[:, lane] * trav_w[sl, lane, None])
                       .astype(dtype)).astype(dtype)
        inter[sl] = (eq & (trav_d[sl, :, None] != 0)).sum(axis=1)
    return eta, inter


def pins_count(parts_dense, dst_dense, kdim: int):
    """pins[e, k] = #{j: parts[e, j] == k}, pins_in the same over the
    destination slots; ids >= kdim are padding."""
    parts_dense = np.asarray(parts_dense).astype(np.int64)
    dst = np.asarray(dst_dense) != 0
    e = parts_dense.shape[0]
    live = parts_dense < kdim
    rows = np.repeat(np.arange(e), parts_dense.shape[1]).reshape(e, -1)
    key = (rows * kdim + np.where(live, parts_dense, 0))
    pins = np.bincount(key[live], minlength=e * kdim).reshape(e, kdim)
    pins_in = np.bincount(key[live & dst],
                          minlength=e * kdim).reshape(e, kdim)
    return pins, pins_in
