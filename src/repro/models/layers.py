"""Transformer building blocks: GQA attention (flash-chunked), MLA
(DeepSeek-V2 compressed KV), SwiGLU MLP, MoE (sort-based capacity dispatch),
embeddings. Pure functions: `*_shapes(cfg)` declares parameter Specs,
`*_apply(params, ...)` computes. No framework dependencies.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (Spec, apply_rope, rms_norm,
                                 swiglu)

F32 = jnp.float32


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def attn_shapes(cfg: ArchConfig, cross: bool = False) -> dict:
    H, KV, DH, D = cfg.n_heads, cfg.n_kv, cfg.d_head, cfg.d_model
    s = {
        "wq": Spec((D, H * DH), ("embed", "heads")),
        "wk": Spec((D, KV * DH), ("embed", "kv_heads")),
        "wv": Spec((D, KV * DH), ("embed", "kv_heads")),
        "wo": Spec((H * DH, D), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec((H * DH,), ("heads",), init="zeros")
        s["bk"] = Spec((KV * DH,), ("kv_heads",), init="zeros")
        s["bv"] = Spec((KV * DH,), ("kv_heads",), init="zeros")
    return s


def qkv_project(p: dict, x, xkv, cfg: ArchConfig):
    H, KV, DH = cfg.n_heads, cfg.n_kv, cfg.d_head
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", xkv, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", xkv, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    B, S = x.shape[:2]
    Skv = xkv.shape[1]
    return (q.reshape(B, S, H, DH), k.reshape(B, Skv, KV, DH),
            v.reshape(B, Skv, KV, DH))


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    q_chunk: int = 1024, k_chunk: int = 1024, plan=None,
                    seq_parallel: bool = False, p_bf16: bool = False,
                    scale: float | None = None):
    """Flash-style chunked attention with online softmax, pure JAX.

    q [B,Sq,H,Dh], k/v [B,Sk,KV,Dh], GQA groups g = H//KV. Query chunks are
    *vectorized* (a leading nq dim) while key chunks stream through one
    sequential scan — the score working set stays q_chunk x k_chunk and,
    unlike a double scan, the nq dim can be sharded over the "model" axis
    (sequence-parallel attention, hillclimb Q1) for archs whose head count
    doesn't divide the TP axis. `p_bf16` (hillclimb M1) casts softmax
    probabilities to bf16 for the PV matmul, halving the dominant
    score-side HBM traffic at negligible accuracy cost (accumulation stays
    f32).
    """
    B, Sq, H, Dh = q.shape
    _, Sk, KV, Dhk = k.shape
    Dv = v.shape[-1]
    g = H // KV
    qc = math.gcd(min(q_chunk, Sq), Sq)
    kc = math.gcd(min(k_chunk, Sk), Sk)
    nq, nk = Sq // qc, Sk // kc
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    qr = q.reshape(B, nq, qc, KV, g, Dh)
    if plan is not None and seq_parallel:
        qr = plan.constraint(qr, "batch", "attn_seq", None, None, None, None)
    kr = jnp.moveaxis(k.reshape(B, nk, kc, KV, Dh), 1, 0)   # [nk,B,kc,KV,Dh]
    vr = jnp.moveaxis(v.reshape(B, nk, kc, KV, Dh), 1, 0)

    iq = jnp.arange(qc)
    ik = jnp.arange(kc)
    qpos = q_offset + (jnp.arange(nq) * qc)[:, None] + iq[None, :]  # [nq,qc]

    def k_body(carry, ki_kv):
        m, l, acc = carry                       # [B,nq,KV,g,qc](,Dh)
        ki, kblk, vblk = ki_kv
        s = jnp.einsum("bnqkgd,bckd->bnkgqc", qr, kblk,
                       preferred_element_type=F32) * scale
        if causal:
            kpos = ki * kc + ik
            mask = qpos[:, :, None] >= kpos[None, None, :]   # [nq,qc,kc]
            s = jnp.where(mask[None, :, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        pv = p.astype(q.dtype) if p_bf16 else p
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnkgqc,bckd->bnkgqd", pv, vblk, preferred_element_type=F32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, nq, KV, g, qc), -jnp.inf, F32)
    l0 = jnp.zeros((B, nq, KV, g, qc), F32)
    a0 = jnp.zeros((B, nq, KV, g, qc, Dv), F32)
    (m, l, acc), _ = jax.lax.scan(k_body, (m0, l0, a0),
                                  (jnp.arange(nk), kr, vr))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    # [B,nq,KV,g,qc,Dv] -> [B,nq,qc,KV,g,Dv] -> [B,Sq,H,Dv]
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5))
    return out.reshape(B, Sq, H, Dv)


def decode_attention(q, kcache, vcache, length=None):
    """Single-step attention over a dense cache. q [B,1,H,Dh],
    cache [B,S,KV,Dh]."""
    B, _, H, Dh = q.shape
    _, S, KV, _ = kcache.shape
    g = H // KV
    qr = q.reshape(B, KV, g, Dh)
    s = jnp.einsum("bkgd,bckd->bkgc", qr, kcache,
                   preferred_element_type=F32) / math.sqrt(Dh)
    if length is not None:
        mask = jnp.arange(S)[None] < length[:, None]
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgc,bckd->bkgd", a, vcache,
                   preferred_element_type=F32)
    return o.reshape(B, 1, H, Dh).astype(q.dtype)


def decode_attention_seqsharded(plan, q, kcache, vcache, length=None):
    """Flash-decoding over a sequence-sharded KV cache: each "model" rank
    holds S/m of the cache, computes a partial softmax, and the partials
    combine with psum — the TPU analogue of flash-decoding, required for
    the 32k/500k decode cells where a replicated cache cannot fit HBM."""
    from jax.sharding import PartitionSpec as P
    mesh = plan.mesh
    if "model" not in mesh.axis_names or plan.rules.get("kv_seq") is None:
        return decode_attention(q, kcache, vcache, length)
    dp = plan.rules["batch"]

    def local(qb, kb, vb):
        B, _, H, Dh = qb.shape
        _, Sl, KV, _ = kb.shape
        g = H // KV
        qr = qb.reshape(B, KV, g, Dh)
        s = jnp.einsum("bkgd,bckd->bkgc", qr, kb,
                       preferred_element_type=F32) / math.sqrt(Dh)
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), "model")
        o = jnp.einsum("bkgc,bckd->bkgd", p.astype(qb.dtype), vb,
                       preferred_element_type=F32)
        o = jax.lax.psum(o, "model") / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(B, 1, H, Dh).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp), P(dp, "model"), P(dp, "model")),
        out_specs=P(dp),
        check_vma=False)(q, kcache, vcache)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------
def mla_shapes(cfg: ArchConfig) -> dict:
    m = cfg.mla
    H, D = cfg.n_heads, cfg.d_model
    return {
        "w_dq": Spec((D, m.q_lora), ("embed", None)),
        "q_norm": Spec((m.q_lora,), (None,), init="ones"),
        "w_uq": Spec((m.q_lora, H * (m.d_nope + m.d_rope)), (None, "heads")),
        "w_dkv": Spec((D, m.kv_lora), ("embed", None)),
        "kv_norm": Spec((m.kv_lora,), (None,), init="ones"),
        "w_kr": Spec((D, m.d_rope), ("embed", None)),
        "w_uk": Spec((m.kv_lora, H * m.d_nope), (None, "heads")),
        "w_uv": Spec((m.kv_lora, H * m.d_v), (None, "heads")),
        "wo": Spec((H * m.d_v, D), ("heads", "embed")),
    }


def mla_project_q(p, x, cfg: ArchConfig, positions):
    m = cfg.mla
    H = cfg.n_heads
    B, S, _ = x.shape
    cq = rms_norm(jnp.einsum("bsd,dq->bsq", x, p["w_dq"]), p["q_norm"])
    q = jnp.einsum("bsq,qh->bsh", cq, p["w_uq"]).reshape(
        B, S, H, m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., : m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_compress_kv(p, x, cfg: ArchConfig, positions):
    """Returns the compressed cache entries: c_kv [B,S,kv_lora],
    k_rope [B,S,d_rope] (shared across heads)."""
    c = rms_norm(jnp.einsum("bsd,dq->bsq", x, p["w_dkv"]), p["kv_norm"])
    kr = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])[:, :, None, :]
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]
    return c, kr


def mla_attention_flash(p, q_nope, q_rope, c_kv, k_rope, cfg: ArchConfig,
                        causal: bool, q_offset=0, plan=None):
    """Hillclimb D2 (EXPERIMENTS.md §Perf): chunked MLA via the flash path.

    Absorbed form in latent space: q' = [W_uk^T q_nope || q_rope] per head,
    k' = [c_kv || k_rope] with ONE shared KV head, values = c_kv; the
    latent combine up-projects after attention. The S x S probability
    matrix never materializes — the baseline `mla_attention` holds
    [B,H,Sq,Sk] f32, the dominant memory term of deepseek train_4k."""
    m = cfg.mla
    H = cfg.n_heads
    B, Sq = q_nope.shape[:2]
    w_uk = p["w_uk"].reshape(m.kv_lora, H, m.d_nope)
    q_eff = jnp.einsum("bshn,qhn->bshq", q_nope, w_uk)
    qq = jnp.concatenate([q_eff, q_rope], axis=-1)      # [B,Sq,H,lora+rope]
    kk = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
    vv = c_kv[:, :, None, :]
    lat = flash_attention(qq, kk, vv, causal=causal, q_offset=q_offset,
                          q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk,
                          plan=plan,
                          scale=1.0 / math.sqrt(m.d_nope + m.d_rope))
    w_uv = p["w_uv"].reshape(m.kv_lora, H, m.d_v)
    return jnp.einsum("bshq,qhv->bshv", lat, w_uv)


def mla_attention(p, q_nope, q_rope, c_kv, k_rope, cfg: ArchConfig,
                  causal: bool, q_offset=0):
    """Absorbed-matrix MLA attention: scores use q_nope.(W_uk c) folded as
    (W_uk^T q_nope).c so only the compressed cache is traversed; values
    combine in latent space then up-project (DeepSeek-V2 Sec. 2.1)."""
    m = cfg.mla
    H = cfg.n_heads
    B, Sq = q_nope.shape[:2]
    Sk = c_kv.shape[1]
    w_uk = p["w_uk"].reshape(m.kv_lora, H, m.d_nope)
    q_eff = jnp.einsum("bshn,qhn->bshq", q_nope, w_uk)       # [B,Sq,H,kv_lora]
    s = (jnp.einsum("bshq,btq->bhst", q_eff, c_kv, preferred_element_type=F32)
         + jnp.einsum("bshr,btr->bhst", q_rope, k_rope,
                      preferred_element_type=F32))
    s = s / math.sqrt(m.d_nope + m.d_rope)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(q_nope.dtype)
    lat = jnp.einsum("bhst,btq->bshq", a, c_kv)              # latent combine
    w_uv = p["w_uv"].reshape(m.kv_lora, H, m.d_v)
    return jnp.einsum("bshq,qhv->bshv", lat, w_uv)           # [B,Sq,H,d_v]


def mla_output(p, o, cfg: ArchConfig):
    B, S, H, Dv = o.shape
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * Dv), p["wo"])


# ---------------------------------------------------------------------------
# MLP + MoE
# ---------------------------------------------------------------------------
def mlp_shapes(cfg: ArchConfig, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    return {
        "w_gate": Spec((D, F), ("embed", "mlp")),
        "w_up": Spec((D, F), ("embed", "mlp")),
        "w_down": Spec((F, D), ("mlp", "embed")),
    }


def mlp_apply(p, x, plan=None):
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g) * u
    if plan is not None and h.ndim == 3:  # megatron TP: hidden over "model"
        h = plan.constraint(h, "batch", None, "mlp")
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def moe_shapes(cfg: ArchConfig) -> dict:
    mo = cfg.moe
    D = cfg.d_model
    s = {
        "router": Spec((D, mo.n_experts), ("embed", None)),
        "we_gate": Spec((mo.n_experts, D, mo.d_ff_expert),
                        ("experts", "embed", "mlp")),
        "we_up": Spec((mo.n_experts, D, mo.d_ff_expert),
                      ("experts", "embed", "mlp")),
        "we_down": Spec((mo.n_experts, mo.d_ff_expert, D),
                        ("experts", "mlp", "embed")),
    }
    if mo.n_shared:
        s["shared"] = mlp_shapes(cfg, d_ff=mo.n_shared * mo.d_ff_expert)
    return s


def moe_apply_local_dispatch(p, x, cfg: ArchConfig,
                             expert_perm: jax.Array | None, plan):
    """Hillclimb D1 (EXPERIMENTS.md §Perf): shard-local MoE dispatch.

    The global sort+scatter dispatch hands XLA a scatter whose indices span
    the whole token axis, so the SPMD partitioner all-gathers the [E,cap,D]
    buffers across the mesh (collective-bound deepseek baseline). Here the
    top-k/sort/scatter runs *inside* shard_map over the DP axes — indices
    are rank-local, zero collectives — producing xe with the capacity dim
    sharded over DP. One constrained einsum then re-shards to (experts->EP,
    cap->DP) for the expert GEMMs; the combine gather is again rank-local.
    """
    from jax.sharding import PartitionSpec as P
    mo = cfg.moe
    B, S, D = x.shape
    E, K = mo.n_experts, mo.top_k
    dp = plan.rules["batch"]
    ndp = 1
    for a in ((dp,) if isinstance(dp, str) else dp):
        ndp *= plan.mesh.shape[a]
    G = B * S
    cap_local = max(8, int(math.ceil(G * K / E * mo.capacity_factor / ndp)))

    router = p["router"]

    def local(xb, router_w):
        b, s, d = xb.shape
        g = b * s
        xf = xb.reshape(g, d)
        logits = jnp.einsum("gd,de->ge", xf, router_w,
                            preferred_element_type=F32)
        gates = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(gates, K)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        if expert_perm is not None:
            topi = expert_perm[topi]
        density = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=F32), axis=0)
        aux = (jnp.sum(density * jnp.mean(gates, axis=0)) * E
               * mo.router_aux_coef)
        aux = jax.lax.pmean(aux, tuple(plan.mesh.axis_names))
        flat_e = topi.reshape(g * K)
        flat_w = topv.reshape(g * K).astype(xb.dtype)
        tok = jnp.repeat(jnp.arange(g, dtype=jnp.int32), K)
        se, payload = jax.lax.sort(
            [flat_e, jnp.arange(g * K, dtype=jnp.int32)], num_keys=1,
            is_stable=True)
        stok = tok[payload]
        seg_start = jnp.concatenate([jnp.ones((1,), bool),
                                     se[1:] != se[:-1]])
        idx = jnp.arange(g * K, dtype=jnp.int32)
        start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(seg_start, idx, 0))
        pos = idx - start
        keep = pos < cap_local
        xe = jnp.zeros((E, cap_local, d), xb.dtype)
        xe = xe.at[jnp.where(keep, se, E),
                   jnp.where(keep, pos, 0)].add(xf[stok], mode="drop")
        meta = dict(se=se, pos=pos, keep=keep, stok=stok,
                    w=flat_w[payload])
        return xe, aux, meta

    def combine(ye, meta, b, s, d):
        g = b * s
        contrib = ye[jnp.where(meta["keep"], meta["se"], 0),
                     jnp.where(meta["keep"], meta["pos"], 0)]
        contrib = jnp.where(meta["keep"][:, None], contrib, 0.0)
        out = jnp.zeros((g, d), ye.dtype).at[meta["stok"]].add(
            contrib * meta["w"][:, None])
        return out.reshape(b, s, d)

    assert B % ndp == 0, "local dispatch requires DP-divisible batch"
    b_loc = B // ndp
    xe, aux, meta = jax.shard_map(
        local, mesh=plan.mesh, in_specs=(P(dp), P()),
        out_specs=(P(None, dp), P(), P(dp)), check_vma=False)(x, router)
    # re-shard once for the expert GEMMs: experts -> EP, capacity -> DP
    xe = plan.constraint(xe, "experts", "batch", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    h = plan.constraint(h, "experts", "batch", "mlp")
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    ye = plan.constraint(ye, "experts", "batch", None)

    out = jax.shard_map(
        lambda yb, mb: combine(yb, mb, b_loc, S, D),
        mesh=plan.mesh, in_specs=(P(None, dp), P(dp)),
        out_specs=P(dp), check_vma=False)(ye.astype(x.dtype), meta)
    if mo.n_shared:
        out = out + mlp_apply(p["shared"], x, plan)
    return out, aux


def moe_apply(p, x, cfg: ArchConfig, expert_perm: jax.Array | None = None,
              plan=None):
    """Sort-based capacity dispatch (GShard-style, no [G,E,C] one-hot):
    tokens sort by chosen expert, scatter into per-expert capacity slots,
    batched expert GEMMs, gather+combine. `expert_perm` (from the hypergraph
    placement planner) permutes the expert axis so co-activated experts land
    on the same EP shard. Returns (out, aux_loss)."""
    mo = cfg.moe
    B, S, D = x.shape
    if plan is not None and plan.rules.get("moe_local_dispatch"):
        dp = plan.rules["batch"]
        ndp = 1
        for a in ((dp,) if isinstance(dp, str) else dp):
            ndp *= plan.mesh.shape[a]
        if B % ndp == 0 and B > 1:
            return moe_apply_local_dispatch(p, x, cfg, expert_perm, plan)
    E, K = mo.n_experts, mo.top_k
    G = B * S
    xf = x.reshape(G, D)

    logits = jnp.einsum("gd,de->ge", xf, p["router"],
                        preferred_element_type=F32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, K)                      # [G,K]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    if expert_perm is not None:
        topi = expert_perm[topi]

    # aux load-balance loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=F32), axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux = jnp.sum(density * density_proxy) * E * mo.router_aux_coef

    cap = max(8, int(math.ceil(G * K / E * mo.capacity_factor)))
    flat_e = topi.reshape(G * K)
    flat_w = topv.reshape(G * K).astype(x.dtype)
    tok = jnp.repeat(jnp.arange(G, dtype=jnp.int32), K)

    se, payload = jax.lax.sort([flat_e, jnp.arange(G * K, dtype=jnp.int32)],
                               num_keys=1, is_stable=True)
    stok = tok[payload]
    # position within expert group
    seg_start = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
    idx = jnp.arange(G * K, dtype=jnp.int32)
    start_pos = jnp.where(seg_start, idx, 0)
    start_of_seg = jax.lax.associative_scan(jnp.maximum, start_pos)
    pos = idx - start_of_seg
    keep = pos < cap

    xe = jnp.zeros((E, cap, D), x.dtype)
    xe = xe.at[jnp.where(keep, se, E), jnp.where(keep, pos, 0)].add(
        xf[stok], mode="drop")
    if plan is not None:  # EP over experts, capacity over the DP axis
        xe = plan.constraint(xe, "experts", "batch", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    if plan is not None:
        h = plan.constraint(h, "experts", "batch", "mlp")
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"])          # [E,cap,D]
    if plan is not None:
        ye = plan.constraint(ye, "experts", "batch", None)

    contrib = ye[jnp.where(keep, se, 0), jnp.where(keep, pos, 0)]
    contrib = jnp.where(keep[:, None], contrib, 0.0)
    out = jnp.zeros((G, D), x.dtype).at[stok].add(
        contrib * flat_w[payload][:, None])
    out = out.reshape(B, S, D)
    if mo.n_shared:
        out = out + mlp_apply(p["shared"], x, plan)
    return out, aux


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------
def embed_shapes(cfg: ArchConfig) -> dict:
    s = {"tok": Spec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                     init="embed", scale=1.0)}
    if cfg.pos == "learned":
        s["pos"] = Spec((cfg.max_seq, cfg.d_model), (None, "embed"),
                        init="embed", scale=0.02)
    if not cfg.tie_embeddings:
        s["unembed"] = Spec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    if cfg.vision_dim:
        s["vis_proj"] = Spec((cfg.vision_dim, cfg.d_model), (None, "embed"))
    return s


def embed_apply(p, tokens, cfg: ArchConfig, positions=None):
    x = p["tok"][tokens]
    if cfg.pos == "learned":
        assert positions is not None
        x = x + p["pos"][positions]
    return x


def unembed_apply(p, x, cfg: ArchConfig):
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    return jnp.einsum("bsd,dv->bsv", x, w)
