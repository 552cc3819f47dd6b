"""Core LM layers: norms, RoPE, (GQA/local/softcap) attention, MLP, MoE.

Pure functional style: ``init_*`` builds a param pytree, ``*_fwd`` applies it.
All matmuls run in the config compute dtype (bf16 by default); softmax,
normalization and reductions accumulate in float32.

Attention supports three execution paths:
  * full        — one einsum, for short sequences;
  * chunked     — lax.scan over query chunks (bounded score memory; the
                  paper-§V.B "fused softmax" discipline applied to attention);
  * decode      — single-token query against a laid-out KV cache.

The KV cache supports two layouts (paper §IV data-layout selection applied to
serving): ``bksd`` = [B, K, S, Dh] (read-friendly) and ``sbkd`` = [S, B, K, Dh]
(update-friendly: a decode step writes a [1, B, K, Dh] row — full native tiles
— instead of B*K strided size-1-sublane slices).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, in_axis=0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_fwd(p, x, cfg: ModelConfig, eps: Optional[float] = None):
    eps = eps or cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float):
    """x: [..., S, n_heads, head_dim]; positions: [..., S] int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                       # [half]
    ang = positions[..., None].astype(jnp.float32) * freqs       # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                             # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(x.dtype)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    D, Q, KV = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": dense_init(ks[0], (D, Q), 0, dt),
        "wk": dense_init(ks[1], (D, KV), 0, dt),
        "wv": dense_init(ks[2], (D, KV), 0, dt),
        "wo": dense_init(ks[3], (Q, D), 0, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Q,), dt)
        p["bk"] = jnp.zeros((KV,), dt)
        p["bv"] = jnp.zeros((KV,), dt)
    return p


def _qkv(p, x, cfg: ModelConfig):
    B, S, _ = x.shape
    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, H, Dh), k.reshape(B, S, K, Dh),
            v.reshape(B, S, K, Dh))


def _scores_mask(q_pos, k_pos, local_window):
    """[Sq, Sk] bool mask: causal, optionally sliding-window."""
    m = k_pos[None, :] <= q_pos[:, None]
    if local_window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < local_window
    return m


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q: [B,Sq,H,Dh], k/v: [B,Sk,K,Dh], mask: [Sq,Sk] or [B,1,1,Sq,Sk]."""
    B, Sq, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cfg.attn_logit_softcap)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return o.reshape(B, Sq, H, Dh)


def attention_fwd(p, x, positions, cfg: ModelConfig, *, local: bool = False,
                  q_chunk: int = 1024, cross_kv=None):
    """Training/prefill attention.  Returns [B,S,D].

    Chunked over queries when S > q_chunk: each chunk computes a bounded
    [B,H,Cq,S] score block (fused-softmax discipline; no [S,S] residency).
    ``cross_kv``: optional (k, v) ([B,T,K,Dh]) for encoder-decoder cross
    attention (no causal mask).
    """
    B, S, D = x.shape
    window = cfg.local_window if local else None
    if cross_kv is not None:
        q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(cfg.num_heads, cfg.head_dim)
        k, v = cross_kv
        Sk = k.shape[1]
        mask = jnp.ones((S, Sk), bool)
        o = _sdpa(q, k, v, mask, cfg)
        return o.reshape(B, S, cfg.q_dim) @ p["wo"]

    q, k, v = _qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if S <= q_chunk:
        mask = _scores_mask(positions[0], positions[0], window)
        o = _sdpa(q, k, v, mask, cfg)
        return o.reshape(B, S, cfg.q_dim) @ p["wo"]

    # chunked: scan over query blocks, K/V stay resident.
    n_chunks = S // q_chunk
    assert S % q_chunk == 0, (S, q_chunk)
    k_pos = positions[0]

    def chunk_body(_, qc_i):
        qc, qpos = qc_i
        mask = _scores_mask(qpos, k_pos, window)
        return None, _sdpa(qc, k, v, mask, cfg)

    q_chunks = q.reshape(B, n_chunks, q_chunk, cfg.num_heads, cfg.head_dim)
    q_chunks = jnp.moveaxis(q_chunks, 1, 0)                 # [n,B,Cq,H,Dh]
    pos_chunks = positions[0].reshape(n_chunks, q_chunk)
    _, o = lax.scan(jax.remat(chunk_body), None, (q_chunks, pos_chunks))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, cfg.q_dim)
    return o @ p["wo"]


# -- KV cache ----------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  layout: str = "bksd", dtype=jnp.bfloat16):
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    shape = ((batch, K, max_len, Dh) if layout == "bksd"
             else (max_len, batch, K, Dh))
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cache_write_masked(cache, k_new, v_new, pos, layout: str):
    """Single-token cache write via a one-hot select along S.

    Used when the cache's sequence dim is sharded over the mesh: a
    dynamic-update-slice on a sharded dim forces GSPMD into involuntary full
    rematerialization (observed in the dry-run), whereas a select/where is a
    purely local elementwise op.  Costs one extra cache-sized write — picked
    per sharding by the steps factory (the paper's layout-vs-access-pattern
    arbitration applied to serving)."""
    assert k_new.shape[1] == 1, "masked write is decode-only"
    if layout == "bksd":
        S = cache["k"].shape[2]
        hit = (jnp.arange(S, dtype=jnp.int32) == pos % S)[None, None, :, None]
        kn = jnp.moveaxis(k_new, 1, 2).astype(cache["k"].dtype)
        vn = jnp.moveaxis(v_new, 1, 2).astype(cache["v"].dtype)
    else:  # sbkd
        S = cache["k"].shape[0]
        hit = (jnp.arange(S, dtype=jnp.int32) == pos % S)[:, None, None, None]
        kn = jnp.moveaxis(k_new, 0, 1).astype(cache["k"].dtype)
        vn = jnp.moveaxis(v_new, 0, 1).astype(cache["v"].dtype)
    return {"k": jnp.where(hit, kn, cache["k"]),
            "v": jnp.where(hit, vn, cache["v"])}


def _cache_write(cache, k_new, v_new, pos, layout: str):
    """k_new/v_new: [B, S_new, K, Dh]; pos: int32 scalar start index
    (taken modulo the cache capacity -> ring-buffer semantics for window
    caches; a full-length cache is unaffected since pos < capacity)."""
    cap = cache["k"].shape[2] if layout == "bksd" else cache["k"].shape[0]
    pos = pos % cap
    if layout == "bksd":
        kn = jnp.moveaxis(k_new, 1, 2)     # [B,K,S_new,Dh]
        vn = jnp.moveaxis(v_new, 1, 2)
        k = lax.dynamic_update_slice(cache["k"], kn.astype(cache["k"].dtype),
                                     (0, 0, pos, 0))
        v = lax.dynamic_update_slice(cache["v"], vn.astype(cache["v"].dtype),
                                     (0, 0, pos, 0))
    else:  # sbkd
        kn = jnp.moveaxis(k_new, 0, 1)     # [S_new,B,K,Dh]
        vn = jnp.moveaxis(v_new, 0, 1)
        k = lax.dynamic_update_slice(cache["k"], kn.astype(cache["k"].dtype),
                                     (pos, 0, 0, 0))
        v = lax.dynamic_update_slice(cache["v"], vn.astype(cache["v"].dtype),
                                     (pos, 0, 0, 0))
    return {"k": k, "v": v}


def attention_decode(p, x, cache, cache_len, cfg: ModelConfig, *,
                     layout: str = "bksd", local: bool = False,
                     cross: bool = False, update: str = "dus",
                     windowed: bool = False):
    """One-token decode.  x: [B,1,D]; cache_len: int32 scalar (tokens already
    in cache).  ``update``: "dus" (dynamic-update-slice; cheap when the S dim
    is unsharded) or "masked" (sharded-S-safe select).
    Returns (y [B,1,D], new_cache)."""
    B = x.shape[0]
    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    if cross:
        q = (x @ p["wq"]).reshape(B, 1, H, Dh)
        new_cache = cache
    else:
        q, k_new, v_new = _qkv(p, x, cfg)
        pos = jnp.full((B, 1), cache_len, jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
        writer = _cache_write_masked if update == "masked" else _cache_write
        new_cache = writer(cache, k_new, v_new, cache_len, layout)

    kc, vc = new_cache["k"], new_cache["v"]
    S = kc.shape[2] if layout == "bksd" else kc.shape[0]
    qg = q.reshape(B, K, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    if layout == "bksd":
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
    else:
        s = jnp.einsum("bkgd,sbkd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
    s = softcap(s, cfg.attn_logit_softcap)
    k_pos = jnp.arange(S)
    if cross:
        valid = k_pos >= 0
    elif windowed:
        # ring-buffer window cache: every filled slot is in-window
        valid = k_pos < jnp.minimum(cache_len + 1, S)
    else:
        valid = k_pos <= cache_len
        if local and cfg.local_window is not None:
            valid &= (cache_len - k_pos) < cfg.local_window
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    if layout == "bksd":
        o = jnp.einsum("bkgs,bksd->bkgd", pr, vc)
    else:
        o = jnp.einsum("bkgs,sbkd->bkgd", pr, vc)
    y = o.reshape(B, 1, cfg.q_dim) @ p["wo"]
    return y, new_cache


def attention_prefill(p, x, positions, cfg: ModelConfig, max_len: int, *,
                      layout: str = "bksd", local: bool = False,
                      q_chunk: int = 1024):
    """Prefill: full forward + populate a KV cache of capacity ``max_len``."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    cache = init_kv_cache(cfg, B, max_len, layout, x.dtype)
    if S > max_len:
        # window cache keeps the last `max_len` tokens, ring-rolled so that
        # token t lives in slot t %% max_len
        shift = (S - max_len) % max_len
        kw = jnp.roll(k[:, S - max_len:], shift, axis=1)
        vw = jnp.roll(v[:, S - max_len:], shift, axis=1)
        cache = _cache_write(cache, kw, vw, jnp.int32(0), layout)
    else:
        cache = _cache_write(cache, k, v, jnp.int32(0), layout)
    window = cfg.local_window if local else None
    if S <= q_chunk:
        mask = _scores_mask(positions[0], positions[0], window)
        o = _sdpa(q, k, v, mask, cfg)
    else:
        n = S // q_chunk
        qc = jnp.moveaxis(q.reshape(B, n, q_chunk, cfg.num_heads, cfg.head_dim), 1, 0)
        pc = positions[0].reshape(n, q_chunk)

        def body(_, qi):
            qq, pp = qi
            m = _scores_mask(pp, positions[0], window)
            return None, _sdpa(qq, k, v, m, cfg)

        _, o = lax.scan(jax.remat(body), None, (qc, pc))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S, cfg.num_heads, cfg.head_dim)
    y = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    return y, cache


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    F = d_ff or cfg.d_ff
    return {
        "w_gate": dense_init(ks[0], (cfg.d_model, F), 0, dt),
        "w_up": dense_init(ks[1], (cfg.d_model, F), 0, dt),
        "w_down": dense_init(ks[2], (F, cfg.d_model), 0, dt),
    }


def _act(cfg: ModelConfig):
    return jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu


def mlp_fwd(p, x, cfg: ModelConfig):
    g = _act(cfg)(x @ p["w_gate"])
    return (g * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (top-k, capacity-bounded, scatter/gather dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    dt = _dtype(cfg)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    p = {
        "router": dense_init(ks[0], (D, E), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), 1, dt),
        "w_up": dense_init(ks[2], (E, D, F), 1, dt),
        "w_down": dense_init(ks[3], (E, F, D), 1, dt),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, cfg.expert_d_ff * cfg.num_shared_experts)
    return p


def moe_fwd(p, x, cfg: ModelConfig):
    """Capacity-bounded top-k MoE with scatter dispatch / gather combine.

    Dispatch avoids the O(T*E*C*D) one-hot einsum: tokens are scattered into a
    per-expert buffer [E*C, D] (memory-bound, zero matmul FLOPs) and results
    gathered back — the MoE analogue of the paper's redundant-access removal.
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    cap = int(cfg.capacity_factor * T * k / E)
    cap = max(8, min(cap, T))
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = lax.top_k(probs, k)                       # [T, k]
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) inside its expert's buffer
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)         # [T, k, E]
    flat = onehot.reshape(T * k, E)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat               # [T*k, E]
    pos = (pos_in_e * flat).sum(-1).reshape(T, k)            # [T, k]
    keep = pos < cap
    slot = jnp.where(keep, sel * cap + pos, E * cap)         # overflow -> dropped

    buf = jnp.zeros((E * cap + 1, D), x.dtype)
    idx = slot.reshape(T * k, 1)
    buf = buf.at[idx[:, 0]].set(jnp.repeat(xt, k, axis=0), mode="drop",
                                unique_indices=False)
    expert_in = buf[:E * cap].reshape(E, cap, D)

    h = _act(cfg)(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # [E, cap, D]

    flat_out = jnp.concatenate(
        [expert_out.reshape(E * cap, D), jnp.zeros((1, D), x.dtype)], 0)
    gathered = flat_out[slot.reshape(-1)].reshape(T, k, D)
    y = (gathered * (weights * keep).astype(x.dtype)[..., None]).sum(1)

    if cfg.num_shared_experts:
        y = y + mlp_fwd(p["shared"], xt, cfg)

    # auxiliary load-balance loss (Switch-style), returned via aux
    density = jnp.mean(jax.nn.one_hot(sel[:, 0], E, dtype=jnp.float32), 0)
    router_prob = jnp.mean(probs, 0)
    aux = E * jnp.sum(density * router_prob)
    return y.reshape(B, S, D), aux


# -- expert-parallel MoE (manual all-to-all under shard_map) -----------------
#
# The scatter/gather dispatch above does not partition under GSPMD (the
# scatter breaks sharding propagation and every expert tensor replicates —
# observed as 100s of GiB/chip of temps in the dry-run).  The production path
# is the classic Switch pipeline, written manually over the mesh:
#
#   tokens sharded over (pod, data, model·seq)  --local scatter-->
#   per-expert buffers [E, C_loc, D]            --all_to_all(model)-->
#   expert shards compute their experts         --all_to_all(model)-->
#   local gather/combine.
#
# Expert weights are EP-sharded over "model" and (optionally) FSDP-sharded
# over data/pod on d_model; the FSDP all-gather is explicit here.

def _moe_local_dispatch(xt, p, cfg: ModelConfig, cap: int):
    """Local top-k routing + scatter into per-expert buffers.
    xt: [T,D] (shard-local).  Returns (buf [E,cap,D], slot, weights, keep, aux)."""
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = lax.top_k(probs, k)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)
    flat = onehot.reshape(T * k, E)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat
    pos = (pos_in_e * flat).sum(-1).reshape(T, k)
    keep = pos < cap
    slot = jnp.where(keep, sel * cap + pos, E * cap)
    buf = jnp.zeros((E * cap + 1, D), xt.dtype)
    buf = buf.at[slot.reshape(-1)].set(jnp.repeat(xt, k, axis=0), mode="drop")
    density = jnp.mean(jax.nn.one_hot(sel[:, 0], E, dtype=jnp.float32), 0)
    aux = E * jnp.sum(density * jnp.mean(probs, 0))
    return buf[:E * cap].reshape(E, cap, D), slot, weights, keep, aux


def moe_fwd_a2a(p, x, cfg: ModelConfig, ctx):
    """Expert-parallel MoE for train/prefill (S sharded over the model axis).

    Must run under ``shard_map`` with manual mesh axes — ``ctx`` (a
    transformer.ShardCtx) provides axis names.  Capacity is per
    (expert, source shard).
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    tp = ctx.model_axis
    M = ctx.model_size
    fsdp_axes = ctx.fsdp_axes

    def body(xb, router, wg, wu, wd, *rest):
        shared = rest if rest else None
        if fsdp_axes:
            router = lax.all_gather(router, fsdp_axes, axis=0, tiled=True)
            wg = lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
            wu = lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
            wd = lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)
        Bl, Sl, _ = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, D)
        cap = max(4, int(cfg.capacity_factor * T * k / E))
        pp = {"router": router}
        buf, slot, weights, keep, aux = _moe_local_dispatch(xt, pp, cfg, cap)
        # exchange: every model shard keeps its E/M experts from all shards
        buf = lax.all_to_all(buf, tp, split_axis=0, concat_axis=1, tiled=True)
        h = _act(cfg)(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)        # [E/M, cap*M, D]
        out = lax.all_to_all(out, tp, split_axis=1, concat_axis=0, tiled=True)
        flat_out = jnp.concatenate(
            [out.reshape(E * cap, D), jnp.zeros((1, D), x.dtype)], 0)
        y = flat_out[slot.reshape(-1)].reshape(T, k, D)
        y = (y * (weights * keep).astype(x.dtype)[..., None]).sum(1)
        if shared is not None:
            sg, su, sd = shared
            if fsdp_axes:
                sg = lax.all_gather(sg, fsdp_axes, axis=0, tiled=True)
                su = lax.all_gather(su, fsdp_axes, axis=0, tiled=True)
                sd = lax.all_gather(sd, fsdp_axes, axis=1, tiled=True)
            y = y + (_act(cfg)(xt @ sg) * (xt @ su)) @ sd
        manual = tuple(ctx.batch_axes) + (tp,)
        aux = lax.pmean(aux, manual)
        return y.reshape(Bl, Sl, D), aux

    from jax.sharding import PartitionSpec as P
    F = ctx.fsdp_axes if ctx.fsdp_axes else None
    ba = ctx.batch_axes if ctx.batch_axes else None
    x_spec = P(ba, tp, None)
    router_spec = P(F, None)
    w_in_spec = P(tp, F, None)      # [E, D, F]
    w_out_spec = P(tp, None, F)     # [E, F, D]
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    in_specs = [x_spec, router_spec, w_in_spec, w_in_spec, w_out_spec]
    if cfg.num_shared_experts:
        args += [p["shared"]["w_gate"], p["shared"]["w_up"],
                 p["shared"]["w_down"]]
        in_specs += [P(F, None), P(F, None), P(None, F)]

    manual_axes = set(a for a in (ctx.batch_axes or ())) | {tp}
    y, aux = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=tuple(in_specs),
        out_specs=(x_spec, P()),
        axis_names=manual_axes,
        check_vma=False,
    )(*args)
    return y, aux
