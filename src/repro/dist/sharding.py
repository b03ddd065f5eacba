"""Parallelism plans: logical-axis -> mesh-axis rules + sharding helpers.

A `Plan` is the single object the rest of the system consults for layout
decisions. It owns the mesh and a `rules` dict mapping *logical* axes
(declared on parameter `Spec`s and activation constraints) to mesh axes:

  batch     -> the data-parallel axes ("data", or ("pod", "data") multi-pod)
  embed     -> "data" under FSDP (params ZeRO-sharded over DP), else None
  heads/kv_heads/mlp/experts/vocab -> "model" (megatron TP / EP / vocab-par)
  kv_seq    -> "model" when the KV cache is sequence-sharded (flash-decode)
  kv_pages  -> "model" for the paged serving KV pool (pages striped over TP)
  attn_seq  -> "model" for sequence-parallel attention (hillclimb Q1)

Boolean feature flags (attn_p_bf16, mla_flash, moe_local_dispatch) ride in
the same dict — model code reads them with `plan.rules.get(...)`; they never
appear as Spec axes so the resolver ignores them.

Resolution itself (divisibility fallback, one-dim-per-mesh-axis) lives in
`models.common._resolve_pspec`; this module only decides the mapping.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from repro.models import common


@dataclasses.dataclass(frozen=True)
class Plan:
    mesh: Mesh
    rules: dict[str, Any]

    # ------------------------------------------------------------- factory
    @classmethod
    def make(cls, mesh: Mesh, *, fsdp: bool = True, seq_shard_kv: bool = True,
             moe_local: bool = False, seq_parallel_attn: bool = False,
             attn_p_bf16: bool = False, mla_flash: bool = False) -> "Plan":
        """Standard 2D (+pod) plan: DP over every non-"model" axis, megatron
        TP over "model", FSDP (params over DP) when `fsdp`."""
        names = tuple(mesh.axis_names)
        if isinstance(mesh, Mesh):
            # the rules are GSPMD constraints (parameter shardings,
            # `constraint`), so the axes are Auto. jax.make_mesh's default
            # Explicit axes type every op's output sharding instead: a row
            # gather from an FSDP table by data-sharded ids would then map
            # "data" to two dims of its result and fail.
            mesh = Mesh(mesh.devices, names,
                        axis_types=(AxisType.Auto,) * len(names))
        dp_axes = tuple(a for a in names if a != "model")
        dp = dp_axes[0] if len(dp_axes) == 1 else dp_axes
        tp = "model" if "model" in names else None
        # FSDP stays intra-pod: the "pod" axis is DCN, too slow for the
        # per-step param all-gathers.
        fsdp_axis = ("data" if "data" in names else dp) if fsdp else None
        rules: dict[str, Any] = {
            "batch": dp,
            "embed": fsdp_axis,
            "heads": tp,
            "kv_heads": tp,
            "mlp": tp,
            "experts": tp,
            "vocab": tp,
            "layers": None,               # scan axis is never sharded
            "kv_seq": tp if seq_shard_kv else None,
            # paged serving KV ([n_pages, page_size, ...]): stripe the
            # physical-page pool over TP; gathers/scatters stay jit-global
            "kv_pages": tp if seq_shard_kv else None,
            "attn_seq": tp if seq_parallel_attn else None,
            "attn_p_bf16": attn_p_bf16 or None,
            "mla_flash": mla_flash or None,
            "moe_local_dispatch": moe_local or None,
        }
        return cls(mesh=mesh, rules=rules)

    # ------------------------------------------------------------ resolvers
    def pspec(self, *axes: str | None) -> PartitionSpec:
        """Resolve logical axis names to a PartitionSpec (no shape knowledge,
        so no divisibility fallback — use `constraint` for activations)."""
        entries = []
        used: set[str] = set()
        for name in axes:
            mapped = self.rules.get(name) if name else None
            if mapped is None:
                entries.append(None)
                continue
            mesh_axes = ((mapped,) if isinstance(mapped, str)
                         else tuple(mapped))
            if any(ax in used for ax in mesh_axes):
                entries.append(None)
                continue
            used.update(mesh_axes)
            entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        while entries and entries[-1] is None:
            entries.pop()
        return PartitionSpec(*entries)

    def sharding(self, *axes: str | None) -> NamedSharding:
        """NamedSharding for logical axes; `plan.sharding()` = replicated."""
        return NamedSharding(self.mesh, self.pspec(*axes))

    def param_shardings(self, spec_tree):
        """NamedShardings for a tree of `Spec`s (divisibility-aware)."""
        return common.shardings(spec_tree, self.rules, self.mesh)

    def param_pspecs(self, spec_tree):
        return common.pspecs(spec_tree, self.rules, self.mesh)

    def constraint(self, x, *axes: str | None):
        """with_sharding_constraint by logical axes, with the same
        divisibility fallback as parameter resolution (a dim that does not
        divide its mesh axes stays replicated instead of erroring)."""
        spec = common.Spec(tuple(x.shape), tuple(axes))
        ps = common._resolve_pspec(spec, self.rules, self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, ps))

    # ------------------------------------------------------------- helpers
    def dp_size(self) -> int:
        dp = self.rules["batch"]
        axes = (dp,) if isinstance(dp, str) else tuple(dp)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def n_devices(self) -> int:
        n = 1
        for a in self.mesh.axis_names:
            n *= self.mesh.shape[a]
        return n
