"""Model assembly: decoder-only LM (+ encoder-decoder and VLM variants)
built from the declared pattern of (mixer, ffn) layer specs.

Homogeneous superblocks scan over a stacked parameter axis ("layers"
logical axis) with remat — one compiled layer body regardless of depth, the
key to tractable dry-run compiles at 60-layer scale. Heterogeneous patterns
(jamba 7-mamba:1-attn, xlstm mlstm/slstm interleave) stack per *pattern
slot*, so each slot's params are homogeneous across superblocks.

Three entry modes share the block code:
  train   — full causal, no caches, chunked CE loss
  prefill — causal, returns per-layer caches
  decode  — one token against caches (seq-sharded KV via flash-decoding)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import layers as L
from repro.models import ssm
from repro.models.common import (Spec, apply_rope, rms_norm, layer_norm,
                                 stack_specs,
                                 softmax_cross_entropy)

F32 = jnp.float32


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
def _mixer_shapes(spec: LayerSpec, cfg: ArchConfig) -> dict:
    return {"attn": L.attn_shapes, "mla": L.mla_shapes,
            "mamba": ssm.mamba_shapes, "mlstm": ssm.mlstm_shapes,
            "slstm": ssm.slstm_shapes}[spec.mixer](cfg)


def _ffn_shapes(spec: LayerSpec, cfg: ArchConfig) -> dict | None:
    if spec.ffn == "mlp":
        return L.mlp_shapes(cfg)
    if spec.ffn == "moe":
        return L.moe_shapes(cfg)
    return None


def _norm_shapes(cfg: ArchConfig) -> dict:
    if cfg.norm == "rms":
        return {"g": Spec((cfg.d_model,), (None,), init="ones")}
    return {"g": Spec((cfg.d_model,), (None,), init="ones"),
            "b": Spec((cfg.d_model,), (None,), init="zeros")}


def _layer_shapes(spec: LayerSpec, cfg: ArchConfig) -> dict:
    s = {"norm1": _norm_shapes(cfg), "mixer": _mixer_shapes(spec, cfg)}
    ffn = _ffn_shapes(spec, cfg)
    if ffn is not None:
        s["norm2"] = _norm_shapes(cfg)
        s["ffn"] = ffn
    return s


def _enc_layer_shapes(cfg: ArchConfig) -> dict:
    return {"norm1": _norm_shapes(cfg), "mixer": L.attn_shapes(cfg),
            "norm2": _norm_shapes(cfg), "ffn": L.mlp_shapes(cfg)}


def _cross_shapes(cfg: ArchConfig) -> dict:
    return {"normx": _norm_shapes(cfg), "cross": L.attn_shapes(cfg)}


def lm_shapes(cfg: ArchConfig) -> dict:
    s: dict[str, Any] = {"embed": L.embed_shapes(cfg),
                         "final_norm": _norm_shapes(cfg)}
    s["stack"] = {
        f"slot{i}": stack_specs(_layer_shapes(spec, cfg), cfg.n_superblocks)
        for i, spec in enumerate(cfg.pattern)
    }
    if cfg.encoder_layers:
        s["stack_cross"] = {
            f"slot{i}": stack_specs(_cross_shapes(cfg), cfg.n_superblocks)
            for i, _ in enumerate(cfg.pattern)
        }
        s["encoder"] = {
            "stack": stack_specs(_enc_layer_shapes(cfg), cfg.encoder_layers),
            "final_norm": _norm_shapes(cfg),
            "pos": Spec((cfg.max_source_positions, cfg.d_model),
                        (None, "embed"), init="embed", scale=0.02),
        }
    for k in range(cfg.first_k_dense):
        s[f"dense{k}"] = _layer_shapes(
            dataclasses.replace(cfg.pattern[0], ffn="mlp"), cfg)
    return s


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _mixer_cache_spec(spec: LayerSpec, cfg: ArchConfig, batch: int,
                      cache_len: int, page_size: int = 0,
                      n_pages: int = 0) -> dict:
    B, S = batch, cache_len
    if spec.mixer == "attn":
        if page_size:  # paged: [n_pages, page_size, ...] + per-slot tables
            return {"k": Spec((n_pages, page_size, cfg.n_kv, cfg.d_head),
                              ("kv_pages", None, "kv_heads", None),
                              init="zeros"),
                    "v": Spec((n_pages, page_size, cfg.n_kv, cfg.d_head),
                              ("kv_pages", None, "kv_heads", None),
                              init="zeros")}
        return {"k": Spec((B, S, cfg.n_kv, cfg.d_head),
                          ("batch", "kv_seq", "kv_heads", None), init="zeros"),
                "v": Spec((B, S, cfg.n_kv, cfg.d_head),
                          ("batch", "kv_seq", "kv_heads", None), init="zeros")}
    if spec.mixer == "mla":
        m = cfg.mla
        if page_size:
            return {"c_kv": Spec((n_pages, page_size, m.kv_lora),
                                 ("kv_pages", None, None), init="zeros"),
                    "k_rope": Spec((n_pages, page_size, m.d_rope),
                                   ("kv_pages", None, None), init="zeros")}
        return {"c_kv": Spec((B, S, m.kv_lora), ("batch", "kv_seq", None),
                             init="zeros"),
                "k_rope": Spec((B, S, m.d_rope), ("batch", "kv_seq", None),
                               init="zeros")}
    if spec.mixer == "mamba":
        mb = cfg.mamba
        di = mb.expand * cfg.d_model
        return {"tail": Spec((B, mb.d_conv - 1, di), ("batch", None, "mlp"),
                             init="zeros"),
                "h": Spec((B, di, mb.d_state), ("batch", "mlp", None),
                          init="zeros")}
    if spec.mixer == "mlstm":
        H, DH = cfg.n_heads, cfg.d_model // cfg.n_heads
        return {"C": Spec((B, H, DH, DH), ("batch", "heads", None, None),
                          init="zeros"),
                "n": Spec((B, H, DH), ("batch", "heads", None), init="zeros")}
    if spec.mixer == "slstm":
        D = cfg.d_model
        z = lambda: Spec((B, D), ("batch", None), init="zeros")
        return {"c": z(), "n": z(), "h": z(), "m": z()}
    raise ValueError(spec.mixer)


def cache_shapes(cfg: ArchConfig, batch: int, cache_len: int, *,
                 page_size: int = 0, n_pages: int = 0) -> dict:
    """Cache spec tree. With `page_size`/`n_pages` the per-token mixer caches
    (attn KV, MLA compressed KV) switch to the paged [n_pages, page_size, ...]
    layout ("kv_pages" leading axis); per-slot constant-size state (SSM/conv
    tails, recurrent states, enc_out) keeps its [batch, ...] slot layout."""
    c: dict[str, Any] = {"stack": {
        f"slot{i}": stack_specs(_mixer_cache_spec(spec, cfg, batch, cache_len,
                                                  page_size, n_pages),
                                cfg.n_superblocks)
        for i, spec in enumerate(cfg.pattern)}}
    for k in range(cfg.first_k_dense):
        c[f"dense{k}"] = _mixer_cache_spec(cfg.pattern[0], cfg, batch,
                                           cache_len, page_size, n_pages)
    if cfg.encoder_layers:
        enc_len = min(cfg.max_source_positions, cache_len)
        c["enc_out"] = Spec((batch, enc_len, cfg.d_model),
                            ("batch", None, "embed"), init="zeros")
    return c


# ---------------------------------------------------------------------------
# mixers
# ---------------------------------------------------------------------------
def _attn_out(p, o):
    B, S, H, DH = o.shape
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * DH), p["wo"])


def _apply_attn(p, x, cfg, plan, mode, positions, cache, pos, pages=None):
    q, k, v = L.qkv_project(p, x, x, cfg)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if plan is not None:  # megatron TP: heads over "model"
        q = plan.constraint(q, "batch", None, "heads", None)
        k = plan.constraint(k, "batch", None, "kv_heads", None)
        v = plan.constraint(v, "batch", None, "kv_heads", None)
    sp, pbf16 = False, False
    if plan is not None:
        msz = plan.mesh.shape.get("model", 1)
        sp = (plan.rules.get("attn_seq") is not None
              and cfg.n_heads % msz != 0
              and (x.shape[1] // math.gcd(cfg.q_chunk, x.shape[1])) % msz == 0)
        pbf16 = bool(plan.rules.get("attn_p_bf16"))
    if mode == "train":
        o = L.flash_attention(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                              k_chunk=cfg.k_chunk, plan=plan,
                              seq_parallel=sp, p_bf16=pbf16)
        return _attn_out(p, o), None
    if mode == "prefill":
        o = L.flash_attention(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                              k_chunk=cfg.k_chunk, plan=plan,
                              seq_parallel=sp, p_bf16=pbf16)
        S, Sc = x.shape[1], cache["k"].shape[1]
        pad = [(0, 0), (0, Sc - S), (0, 0), (0, 0)]
        new = {"k": jnp.pad(k, pad).astype(cache["k"].dtype),
               "v": jnp.pad(v, pad).astype(cache["v"].dtype)}
        return _attn_out(p, o), new
    # decode: update + flash-decode over (paged / possibly seq-sharded) cache
    if pages is not None:
        table, psize = pages
        posv = _pos_vec(pos, q.shape[0])
        kc = _paged_update(cache["k"], k.astype(cache["k"].dtype), posv,
                           table, psize)
        vc = _paged_update(cache["v"], v.astype(cache["v"].dtype), posv,
                           table, psize)
        o = L.decode_attention(q, _paged_gather(kc, table),
                               _paged_gather(vc, table), length=posv + 1)
        return _attn_out(p, o), {"k": kc, "v": vc}
    o, kc, vc = _decode_attn_update(plan, q, k.astype(cache["k"].dtype),
                                    v.astype(cache["v"].dtype),
                                    cache["k"], cache["v"], pos)
    return _attn_out(p, o), {"k": kc, "v": vc}


def _dp_or_none(plan, batch: int):
    """DP axes for shard_map in_specs, None when batch doesn't divide
    (long_500k global_batch=1)."""
    dp = plan.rules["batch"]
    axes = (dp,) if isinstance(dp, str) else tuple(dp)
    n = 1
    for a in axes:
        n *= plan.mesh.shape[a]
    return dp if batch % n == 0 else None


def _pos_vec(pos, batch: int):
    """Normalize a decode position (scalar or per-slot [B] vector) to [B]."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


def _paged_update(cache, new, posv, table, psize: int):
    """Per-row write into a paged cache. cache [NP, psize, ...], new
    [B, 1, ...], posv [B], table [B, P]. Unallocated table entries are an
    out-of-range sentinel (>= NP), so their writes drop — inactive slots
    never touch physical pages."""
    B = new.shape[0]
    page = table[jnp.arange(B), posv // psize]
    return cache.at[page, posv % psize].set(new[:, 0], mode="drop")


def _paged_gather(cache, table):
    """Materialize each slot's logical [P*psize, ...] view of its pages.
    Sentinel entries clamp to an arbitrary physical page; callers mask by
    per-slot length so the clamped rows never contribute."""
    B, P = table.shape
    g = cache[jnp.clip(table, 0, cache.shape[0] - 1)]
    return g.reshape(B, P * cache.shape[1], *cache.shape[2:])


def _decode_attn_update(plan, q, k_new, v_new, kcache, vcache, pos):
    """Write (k_new, v_new) at per-row `pos` and attend. `pos` may be a
    scalar (synchronized static batch) or a [B] vector (continuous batching:
    every slot sits at its own position). When the cache sequence dim is
    sharded over "model", both the per-row scatter and the flash-decode
    partial softmax run rank-local inside shard_map (paper-free
    beyond-baseline: this is flash-decoding adapted to SPMD TPU)."""
    from jax.sharding import PartitionSpec as P
    posv = _pos_vec(pos, q.shape[0])
    seq_sharded = (plan is not None and "model" in plan.mesh.axis_names
                   and plan.rules.get("kv_seq") is not None
                   and kcache.shape[1] % plan.mesh.shape["model"] == 0)
    if not seq_sharded:
        rows = jnp.arange(q.shape[0])
        kc = kcache.at[rows, posv].set(k_new[:, 0])
        vc = vcache.at[rows, posv].set(v_new[:, 0])
        o = L.decode_attention(q, kc, vc, length=posv + 1)
        return o, kc, vc

    mesh = plan.mesh
    dp = _dp_or_none(plan, q.shape[0])

    def local(qb, knb, vnb, kb, vb, posb):
        B, _, H, Dh = qb.shape
        _, Sl, KV, _ = kb.shape
        g = H // KV
        r = jax.lax.axis_index("model")
        lpos = posb - r * Sl                                   # [B]
        in_rng = (lpos >= 0) & (lpos < Sl)
        safe = jnp.where(in_rng, lpos, Sl)  # off-rank rows drop
        rows = jnp.arange(B)
        kb = kb.at[rows, safe].set(knb[:, 0], mode="drop")
        vb = vb.at[rows, safe].set(vnb[:, 0], mode="drop")
        gpos = r * Sl + jnp.arange(Sl)
        valid = gpos[None, :] <= posb[:, None]                 # [B, Sl]
        qr = qb.reshape(B, KV, g, Dh)
        s = jnp.einsum("bkgd,bckd->bkgc", qr, kb,
                       preferred_element_type=F32) / math.sqrt(Dh)
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
        m = jax.lax.pmax(jnp.max(s, axis=-1), "model")
        p_ = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(p_, axis=-1), "model")
        o = jnp.einsum("bkgc,bckd->bkgd", p_.astype(qb.dtype), vb,
                       preferred_element_type=F32)
        o = jax.lax.psum(o, "model") / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(B, 1, H, Dh).astype(qb.dtype), kb, vb

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp), P(dp), P(dp), P(dp, "model"), P(dp, "model"),
                  P(dp)),
        out_specs=(P(dp), P(dp, "model"), P(dp, "model")),
        check_vma=False)(q, k_new, v_new, kcache, vcache, posv)


def _apply_mla(p, x, cfg, plan, mode, positions, cache, pos, pages=None):
    q_nope, q_rope = L.mla_project_q(p, x, cfg, positions)
    c_new, kr_new = L.mla_compress_kv(p, x, cfg, positions)
    if plan is not None:  # TP: query heads over "model"
        q_nope = plan.constraint(q_nope, "batch", None, "heads", None)
        q_rope = plan.constraint(q_rope, "batch", None, "heads", None)
    use_flash = plan is not None and plan.rules.get("mla_flash")
    mla_fn = (lambda *a, **kw: L.mla_attention_flash(*a, plan=plan, **kw)) \
        if use_flash else L.mla_attention
    if mode == "train":
        o = mla_fn(p, q_nope, q_rope, c_new, kr_new, cfg, causal=True)
        return L.mla_output(p, o, cfg), None
    if mode == "prefill":
        o = mla_fn(p, q_nope, q_rope, c_new, kr_new, cfg, causal=True)
        S, Sc = x.shape[1], cache["c_kv"].shape[1]
        new = {"c_kv": jnp.pad(c_new, [(0, 0), (0, Sc - S), (0, 0)]
                               ).astype(cache["c_kv"].dtype),
               "k_rope": jnp.pad(kr_new, [(0, 0), (0, Sc - S), (0, 0)]
                                 ).astype(cache["k_rope"].dtype)}
        return L.mla_output(p, o, cfg), new
    o, cc, krc = _mla_decode_update(plan, p, q_nope, q_rope,
                                    c_new.astype(cache["c_kv"].dtype),
                                    kr_new.astype(cache["k_rope"].dtype),
                                    cache["c_kv"], cache["k_rope"],
                                    pos, cfg, pages)
    return L.mla_output(p, o, cfg), {"c_kv": cc, "k_rope": krc}


def _mla_decode_update(plan, p, q_nope, q_rope, c_new, kr_new, c_cache,
                       kr_cache, pos, cfg, pages=None):
    """Absorbed-matrix MLA flash-decode over the (paged / seq-sharded)
    compressed cache: scores q_eff.c + q_rope.k_rope, values combine in
    latent space. `pos` is scalar or per-slot [B]."""
    from jax.sharding import PartitionSpec as P
    m = cfg.mla
    H = cfg.n_heads
    B = q_nope.shape[0]
    posv = _pos_vec(pos, B)
    w_uk = p["w_uk"].reshape(m.kv_lora, H, m.d_nope)
    q_eff = jnp.einsum("bshn,qhn->bshq", q_nope, w_uk)[:, 0]   # [B,H,lora]
    qr = q_rope[:, 0]                                          # [B,H,rope]
    scale = 1.0 / math.sqrt(m.d_nope + m.d_rope)

    seq_sharded = (pages is None and plan is not None
                   and "model" in plan.mesh.axis_names
                   and plan.rules.get("kv_seq") is not None
                   and c_cache.shape[1] % plan.mesh.shape["model"] == 0)

    def attend(qe, qrope, cc, krc, posb, axis=None, rank0=0):
        Sl = cc.shape[1]
        gpos = rank0 + jnp.arange(Sl)
        s = (jnp.einsum("bhq,btq->bht", qe, cc, preferred_element_type=F32)
             + jnp.einsum("bhr,btr->bht", qrope, krc,
                          preferred_element_type=F32)) * scale
        s = jnp.where(gpos[None, None, :] <= posb[:, None, None], s,
                      -jnp.inf)
        m_loc = jnp.max(s, axis=-1)
        if axis:
            m_g = jax.lax.pmax(m_loc, axis)
        else:
            m_g = m_loc
        pw = jnp.exp(s - m_g[..., None])
        l = jnp.sum(pw, axis=-1)
        lat = jnp.einsum("bht,btq->bhq", pw.astype(cc.dtype), cc,
                         preferred_element_type=F32)
        if axis:
            l = jax.lax.psum(l, axis)
            lat = jax.lax.psum(lat, axis)
        return (lat / jnp.maximum(l, 1e-30)[..., None])

    if pages is not None:
        table, psize = pages
        cc = _paged_update(c_cache, c_new, posv, table, psize)
        krc = _paged_update(kr_cache, kr_new, posv, table, psize)
        lat = attend(q_eff, qr, _paged_gather(cc, table),
                     _paged_gather(krc, table), posv)
    elif not seq_sharded:
        rows = jnp.arange(B)
        cc = c_cache.at[rows, posv].set(c_new[:, 0])
        krc = kr_cache.at[rows, posv].set(kr_new[:, 0])
        lat = attend(q_eff, qr, cc, krc, posv)
    else:
        mesh = plan.mesh
        dp = _dp_or_none(plan, q_nope.shape[0])

        def local(qe, qrope, cnb, krnb, cb, krb, posb):
            Sl = cb.shape[1]
            r = jax.lax.axis_index("model")
            lpos = posb - r * Sl                               # [B]
            in_rng = (lpos >= 0) & (lpos < Sl)
            safe = jnp.where(in_rng, lpos, Sl)  # off-rank rows drop
            rows = jnp.arange(cb.shape[0])
            cb = cb.at[rows, safe].set(cnb[:, 0], mode="drop")
            krb = krb.at[rows, safe].set(krnb[:, 0], mode="drop")
            lat = attend(qe, qrope, cb, krb, posb, axis="model",
                         rank0=r * Sl)
            return lat, cb, krb

        lat, cc, krc = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp), P(dp), P(dp), P(dp), P(dp, "model"),
                      P(dp, "model"), P(dp)),
            out_specs=(P(dp), P(dp, "model"), P(dp, "model")),
            check_vma=False)(q_eff, qr, c_new, kr_new, c_cache, kr_cache, posv)

    w_uv = p["w_uv"].reshape(m.kv_lora, H, m.d_v)
    o = jnp.einsum("bhq,qhv->bhv", lat.astype(q_nope.dtype), w_uv)
    return o[:, None], cc, krc


def _apply_mixer(spec: LayerSpec, p, x, cfg, plan, mode, positions, cache,
                 pos, pages=None):
    if spec.mixer == "attn":
        return _apply_attn(p, x, cfg, plan, mode, positions, cache, pos,
                           pages)
    if spec.mixer == "mla":
        return _apply_mla(p, x, cfg, plan, mode, positions, cache, pos,
                          pages)
    def _cast(new):
        if new is None or cache is None:
            return new
        return {k: v.astype(cache[k].dtype) for k, v in new.items()}

    if spec.mixer == "mamba":
        state = None if mode in ("train", "prefill") else \
            (cache["tail"], cache["h"])
        out, (tail, h) = ssm.mamba_apply(p, x, cfg, state=state, plan=plan)
        new = {"tail": tail, "h": h} if mode != "train" else None
        return out, _cast(new)
    if spec.mixer == "mlstm":
        state = None if mode in ("train", "prefill") else \
            (cache["C"], cache["n"])
        out, (C, n) = ssm.mlstm_apply(p, x, cfg, state=state)
        new = {"C": C, "n": n} if mode != "train" else None
        return out, _cast(new)
    if spec.mixer == "slstm":
        state = None if mode in ("train", "prefill") else \
            (cache["c"], cache["n"], cache["h"], cache["m"])
        out, (c, n, h, m_) = ssm.slstm_apply(p, x, cfg, state=state)
        new = {"c": c, "n": n, "h": h, "m": m_} if mode != "train" else None
        return out, _cast(new)
    raise ValueError(spec.mixer)


def _apply_layer(spec: LayerSpec, p, x, cfg, plan, mode, positions, cache,
                 pos, cross_p=None, enc_out=None, expert_perm=None,
                 pages=None):
    aux = jnp.float32(0.0)
    h = _norm(p["norm1"], x, cfg)
    mix, new_cache = _apply_mixer(spec, p["mixer"], h, cfg, plan, mode,
                                  positions, cache, pos, pages)
    x = x + mix
    if cross_p is not None and enc_out is not None:
        hx = _norm(cross_p["normx"], x, cfg)
        q, k, v = L.qkv_project(cross_p["cross"], hx, enc_out, cfg)
        o = L.flash_attention(q, k, v, causal=False,
                              q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)
        x = x + _attn_out(cross_p["cross"], o)
    if "ffn" in p:
        h = _norm(p["norm2"], x, cfg)
        if spec.ffn == "moe":
            out, a = L.moe_apply(p["ffn"], h, cfg, expert_perm, plan)
            aux = aux + a
        else:
            out = L.mlp_apply(p["ffn"], h, plan)
        x = x + out
    if plan is not None:
        x = plan.constraint(x, "batch", None, None)
    return x, new_cache, aux


def _norm(p, x, cfg):
    return rms_norm(x, p["g"]) if cfg.norm == "rms" else \
        layer_norm(x, p["g"], p["b"])


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------
def _encoder_forward(params, frames, cfg, plan):
    """Whisper-style encoder over stub frame embeddings [B, T, D]."""
    T = frames.shape[1]
    x = frames + params["encoder"]["pos"][:T]

    def body_nc(x, sp):
        h = _norm(sp["norm1"], x, cfg)
        q, k, v = L.qkv_project(sp["mixer"], h, h, cfg)
        o = L.flash_attention(q, k, v, causal=False, q_chunk=cfg.q_chunk,
                              k_chunk=cfg.k_chunk)
        x = x + _attn_out(sp["mixer"], o)
        h = _norm(sp["norm2"], x, cfg)
        return x + L.mlp_apply(sp["ffn"], h, plan), None

    x, _ = jax.lax.scan(body_nc, x, params["encoder"]["stack"])
    return _norm(params["encoder"]["final_norm"], x, cfg)


def _positions(pos, S: int):
    """Sequence positions for the current chunk: [S] when `pos` is None or
    scalar, [B, S] when `pos` is a per-slot [B] vector (continuous decode:
    each batch row sits at its own position)."""
    if pos is None:
        return jnp.arange(S)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return pos + jnp.arange(S)
    return pos[:, None] + jnp.arange(S)[None, :]


def forward(params, tokens, cfg: ArchConfig, plan=None, *, mode="train",
            cache=None, pos=None, vision=None, enc_frames=None,
            expert_perm=None, remat=True, page_table=None,
            page_size: int = 0):
    """Returns (hidden [B,S,D], new_cache, aux_loss). `pos` may be a scalar
    (synchronized decode) or a [B] vector of per-slot positions; with
    `page_table` [B, P] (+ static `page_size`) the decode-mode KV updates go
    through the paged block-table layout instead of the dense [B, S] one."""
    B, S = tokens.shape
    pages = (page_table, page_size) if page_table is not None else None
    x = L.embed_apply(params["embed"], tokens, cfg,
                      positions=_positions(pos, S)
                      if cfg.pos == "learned" else None)
    if vision is not None and cfg.vision_dim:
        vx = jnp.einsum("bpv,vd->bpd", vision, params["embed"]["vis_proj"])
        x = jnp.concatenate([vx, x], axis=1)
        S = x.shape[1]
    if plan is not None:
        x = plan.constraint(x, "batch", None, None)

    positions = _positions(pos, S)
    enc_out = None
    if cfg.encoder_layers:
        if mode == "decode":
            enc_out = cache["enc_out"]
        else:
            assert enc_frames is not None
            enc_out = _encoder_forward(params, enc_frames, cfg, plan)

    aux = jnp.float32(0.0)
    # unscanned leading dense layers (deepseek first_k_dense)
    for k in range(cfg.first_k_dense):
        c = cache[f"dense{k}"] if cache is not None else None
        x, nc, a = _apply_layer(
            dataclasses.replace(cfg.pattern[0], ffn="mlp"),
            params[f"dense{k}"], x, cfg, plan, mode, positions, c, pos,
            pages=pages)
        aux += a
        if cache is not None and nc is not None:
            cache = dict(cache)
            cache[f"dense{k}"] = nc

    cross_stack = params.get("stack_cross")

    def body(carry, xs):
        x, aux = carry
        slot_params, slot_caches, slot_cross = xs
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            key = f"slot{i}"
            c = slot_caches[key] if slot_caches is not None else None
            xp = slot_cross[key] if slot_cross is not None else None
            x, nc, a = _apply_layer(spec, slot_params[key], x, cfg, plan,
                                    mode, positions, c, pos, xp, enc_out,
                                    expert_perm, pages)
            aux = aux + a
            new_caches[key] = nc
        return (x, aux), new_caches

    body_fn = jax.checkpoint(body) if (mode == "train" and remat) else body
    cache_stack = cache["stack"] if cache is not None else None
    (x, aux), new_stack = jax.lax.scan(
        body_fn, (x, aux), (params["stack"], cache_stack, cross_stack))

    x = _norm(params["final_norm"], x, cfg)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["stack"] = new_stack
        if cfg.encoder_layers and mode != "decode":
            new_cache["enc_out"] = enc_out.astype(cache["enc_out"].dtype)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def chunked_ce_loss(x, params, labels, cfg: ArchConfig, chunk: int = 512,
                    z_loss: float = 1e-4):
    """CE over sequence chunks — never materializes [B, S, V] logits."""
    B, S, D = x.shape
    chunk = math.gcd(min(chunk, S), S)
    nc = S // chunk
    xr = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)
    lr = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    def body(acc, xs):
        xc, lc = xs
        logits = L.unembed_apply(params["embed"], xc, cfg)
        mask = (lc >= 0).sum()
        loss = softmax_cross_entropy(logits, lc, z_loss) * jnp.maximum(mask, 1)
        return (acc[0] + loss, acc[1] + mask), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)),
                                 (xr, lr))
    return tot / jnp.maximum(cnt, 1)


def loss_fn(params, batch, cfg: ArchConfig, plan=None, expert_perm=None,
            remat=True):
    """batch: dict(tokens [B,S], labels [B,S], + optional vision/enc_frames)."""
    x, _, aux = forward(params, batch["tokens"], cfg, plan, mode="train",
                        vision=batch.get("vision"),
                        enc_frames=batch.get("enc_frames"),
                        expert_perm=expert_perm, remat=remat)
    lbl = batch["labels"]
    if batch.get("vision") is not None and cfg.vision_dim:
        pad = jnp.full((lbl.shape[0], x.shape[1] - lbl.shape[1]), -1,
                       lbl.dtype)
        lbl = jnp.concatenate([pad, lbl], axis=1)
    ce = chunked_ce_loss(x, params, lbl, cfg)
    return ce + aux, {"ce": ce, "aux": aux}


def prefill(params, tokens, cache, cfg: ArchConfig, plan=None, *,
            vision=None, enc_frames=None, expert_perm=None):
    """Fills `cache` (zeros, cache_len >= S); returns (last_logits, cache)."""
    x, new_cache, _ = forward(params, tokens, cfg, plan, mode="prefill",
                              cache=cache, vision=vision,
                              enc_frames=enc_frames, expert_perm=expert_perm)
    logits = L.unembed_apply(params["embed"], x[:, -1:], cfg)
    return logits[:, 0], new_cache


def decode_step(params, token, pos, cache, cfg: ArchConfig, plan=None,
                expert_perm=None, page_table=None, page_size: int = 0):
    """token [B,1] int32, pos scalar int32 OR per-slot [B] int32 vector
    (continuous batching). With `page_table` [B, P] + static `page_size` the
    KV caches are paged (see `cache_shapes(page_size=..., n_pages=...)`).
    Returns (logits [B,V], cache)."""
    x, new_cache, _ = forward(params, token, cfg, plan, mode="decode",
                              cache=cache, pos=pos, expert_perm=expert_perm,
                              page_table=page_table, page_size=page_size)
    logits = L.unembed_apply(params["embed"], x, cfg)
    return logits[:, 0], new_cache
