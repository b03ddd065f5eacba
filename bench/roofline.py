"""Peaks by device kind, and the least time a kernel's work could take on
them. Work is counted from the problem's shapes, not from how a kernel
does it, so a rewritten kernel is measured against the same work."""
from __future__ import annotations

from pathlib import Path

import harness


def peaks(device_kind: str, bench: Path = harness.BENCH) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown kind is
    an error, never a default."""
    table = harness.load_json(bench / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: "
                       f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]


def gains_min_s(slots: int, n_nodes: int, kcap: int, pk: dict) -> float:
    """One gains call: every live incidence slot (one per pin) adds its
    edge's weight times that edge's row of ``kcap`` partition flags into
    its node's row. Operations ``2 slots kcap``; bytes: the flag rows read
    (``4 slots kcap``), the slots' edge ids and weights (``8 slots``) and
    the ``[n_nodes, kcap]`` float32 result written."""
    flops = 2.0 * slots * kcap
    nbytes = 4.0 * slots * kcap + 8.0 * slots + 4.0 * n_nodes * kcap
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
