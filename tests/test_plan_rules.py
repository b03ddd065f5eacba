"""Plan rule-resolution unit tests beyond the seed's `test_dist.py` checks:
fsdp on/off, duplicate mesh axes, non-divisible dims replicating, and the
small-batch `_bsh` fallback. Uses AbstractMesh so multi-axis meshes resolve
without forcing host devices."""
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.dist.sharding import Plan
from repro.launch.steps import _bsh, _dp_size
from repro.models.common import Spec, _resolve_pspec


def _mesh(data=2, model=4, pod=None):
    sizes, names = (data, model), ("data", "model")
    if pod is not None:
        sizes, names = (pod,) + sizes, ("pod",) + names
    return AbstractMesh(sizes, names)


def test_fsdp_on_shards_embed_over_data():
    plan = Plan.make(_mesh())
    s = Spec((64, 128), ("embed", "mlp"))
    assert _resolve_pspec(s, plan.rules, plan.mesh) == P("data", "model")


def test_fsdp_off_replicates_embed():
    plan = Plan.make(_mesh(), fsdp=False)
    assert plan.rules["embed"] is None
    s = Spec((64, 128), ("embed", "mlp"))
    assert _resolve_pspec(s, plan.rules, plan.mesh) == P(None, "model")


def test_duplicate_mesh_axis_earlier_dim_wins():
    # experts and mlp both map to "model": EP keeps it, the TP dim drops
    plan = Plan.make(_mesh())
    s = Spec((16, 64, 32), ("experts", "embed", "mlp"))
    assert _resolve_pspec(s, plan.rules, plan.mesh) == P("model", "data")


def test_non_divisible_dim_replicates():
    plan = Plan.make(_mesh(data=2, model=4))
    # 6 heads on a 4-way model axis -> replicated
    assert _resolve_pspec(Spec((6,), ("heads",)), plan.rules,
                          plan.mesh) == P()
    # qwen2 smoke: 2 KV heads on 4-way model -> replicated, 4 heads shard
    assert _resolve_pspec(Spec((2, 16), ("kv_heads", None)), plan.rules,
                          plan.mesh) == P()
    assert _resolve_pspec(Spec((4, 16), ("heads", None)), plan.rules,
                          plan.mesh) == P("model")


def test_multi_pod_batch_spans_pod_and_data():
    plan = Plan.make(_mesh(pod=2))
    assert tuple(plan.rules["batch"]) == ("pod", "data")
    assert _dp_size(plan) == 4
    assert plan.sharding("batch", None).spec == P(("pod", "data"))
    # fsdp stays intra-pod: params never all-gather over DCN
    assert plan.rules["embed"] == "data"


def test_bsh_small_batch_fallback():
    plan = Plan.make(_mesh(data=4, model=2))
    assert _dp_size(plan) == 4
    # divisible batch shards over DP
    assert _bsh(plan, 8, 2).spec == P("data")
    # non-divisible batch (e.g. long_500k B=1) falls back to replicated
    assert _bsh(plan, 1, 2).spec == P()
    assert _bsh(plan, 6, 3).spec == P()


def test_flag_rules_gate_model_features():
    plan = Plan.make(_mesh())
    assert plan.rules["kv_seq"] == "model"       # seq_shard_kv default on
    assert plan.rules["attn_seq"] is None
    assert not plan.rules.get("attn_p_bf16")
    assert not plan.rules.get("mla_flash")
    assert not plan.rules.get("moe_local_dispatch")
    plan2 = Plan.make(_mesh(), seq_shard_kv=False, seq_parallel_attn=True,
                      attn_p_bf16=True, mla_flash=True, moe_local=True)
    assert plan2.rules["kv_seq"] is None
    assert plan2.rules["attn_seq"] == "model"
    assert plan2.rules["attn_p_bf16"] and plan2.rules["mla_flash"]
    assert plan2.rules["moe_local_dispatch"]


def test_sharding_helpers_replicate_by_default():
    plan = Plan.make(_mesh())
    assert plan.sharding().spec == P()
    assert plan.pspec("batch", None, "mlp") == P("data", None, "model")
    # a mesh axis shards at most one dim in a single pspec
    assert plan.pspec("heads", "mlp") == P("model")
    assert plan.n_devices() == 8 and plan.dp_size() == 2


def test_param_shardings_tree_resolution():
    plan = Plan.make(_mesh())
    tree = {"w": Spec((8, 64), ("vocab", "embed")),
            "b": Spec((3,), ("heads",))}          # 3 % 4 != 0 -> replicated
    ps = plan.param_pspecs(tree)
    assert ps["w"] == P("model", "data")
    assert ps["b"] == P()
