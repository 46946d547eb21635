"""Shared model primitives (functional style: explicit param dicts).

Attention mixers route through the execution-mode dispatch in
``kernels.ops`` (mode resolved per layer by the planner rules in
``repro.plan.heuristics``) so every architecture can run the paper's three
execution systems (NON_STREAM / LAYER_STREAM / TILE_STREAM) — the
StreamDCIM technique is a first-class framework feature, not a bolt-on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import AttnKind, ExecutionMode, ModelConfig, pad_to
from repro.kernels import ops, ref

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm_init(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    return {"gamma": jnp.ones((dim or cfg.d_model,), _pdtype(cfg))}


def rms_norm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return ref.rms_norm(x, params["gamma"], eps=eps)


def layer_norm_init(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim or cfg.d_model
    return {"gamma": jnp.ones((d,), _pdtype(cfg)),
            "beta": jnp.zeros((d,), _pdtype(cfg))}


def layer_norm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype) * params["gamma"].astype(x.dtype)
            + params["beta"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding (vocab padded to a multiple of 128 for clean sharding/MXU tiles)
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig, vocab: Optional[int] = None,
               dim: Optional[int] = None) -> Params:
    v = pad_to(vocab or cfg.vocab_size, 128)
    d = dim or cfg.d_model
    p = {"embedding": dense_init(key, (v, d), _pdtype(cfg), scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(key, 1), (d, v),
                                  _pdtype(cfg))
    return p


def embed_lookup(params: Params, tokens: jax.Array) -> jax.Array:
    from repro.distributed.hints import constrain
    return constrain(params["embedding"][tokens], "embed_out")


def unembed(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embedding"].T
    else:
        w = params["unembed"]
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# RoPE (incl. qwen2-vl M-RoPE: per-section (t, h, w) frequency interleave)
# ---------------------------------------------------------------------------

def rope_tables_for(cfg: ModelConfig, seq_len: int, offset: int = 0,
                    head_dim: Optional[int] = None):
    return ref.rope_tables(seq_len, head_dim or cfg.head_dim,
                           theta=cfg.rope_theta, offset=offset)


def mrope_tables(cfg: ModelConfig, positions: jax.Array,
                 head_dim: Optional[int] = None):
    """positions: (3, B, S) — t/h/w position streams (text: all equal).

    Returns sin/cos shaped (B, S, hd//2): section s of the frequency bands
    uses position stream s (M-RoPE, arXiv:2409.12191).
    """
    hd = head_dim or cfg.head_dim
    half = hd // 2
    freqs = 1.0 / (cfg.rope_theta
                   ** (jnp.arange(half, dtype=jnp.float32) / half))
    sections = cfg.mrope_sections or (half,)
    idx = []
    for s, n in enumerate(sections):
        idx.extend([s] * n)
    idx = jnp.asarray(idx[:half], jnp.int32)
    pos_sel = positions[idx]                     # (half, B, S) band -> stream
    ang = jnp.moveaxis(pos_sel, 0, -1) * freqs   # (B, S, half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope_bsd(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, H, S, hd); sin/cos: (S, hd//2) or (B, S, hd//2)."""
    half = x.shape[-1] // 2
    if sin.ndim == 2:
        sin_b = sin[None, None]
        cos_b = cos[None, None]
    else:
        sin_b = sin[:, None]
        cos_b = cos[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    sin_b = sin_b.astype(x.dtype)
    cos_b = cos_b.astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos_b - x2 * sin_b, x2 * cos_b + x1 * sin_b], axis=-1)


# ---------------------------------------------------------------------------
# Attention mixer (dense GQA — the paper-technique carrier)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, d_model: Optional[int] = None,
                   num_heads: Optional[int] = None,
                   num_kv_heads: Optional[int] = None,
                   head_dim: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hq = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    hd = head_dim or cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq, hd), _pdtype(cfg)),
        "wk": dense_init(ks[1], (d, hkv, hd), _pdtype(cfg)),
        "wv": dense_init(ks[2], (d, hkv, hd), _pdtype(cfg)),
        "wo": dense_init(ks[3], (hq, hd, d), _pdtype(cfg)),
    }
    if cfg.use_qk_norm:
        p["q_gamma"] = jnp.ones((hd,), _pdtype(cfg))
        p["k_gamma"] = jnp.ones((hd,), _pdtype(cfg))
    return p


def attention_forward(params: Params, cfg: ModelConfig, x: jax.Array, *,
                      x_kv: Optional[jax.Array] = None,
                      sin=None, cos=None, causal: bool = True,
                      mode: Optional[ExecutionMode] = None,
                      use_pallas: bool = False,
                      q_offset: int = 0) -> jax.Array:
    """Full attention sublayer on pre-normed x.  x_kv (pre-normed KV-side
    activations) defaults to x (self-attention); pass the other modality /
    encoder output for cross-attention — the kernel generates K/V from it on
    the fly in TILE_STREAM mode.

    Mode resolution goes through the planner's per-layer rule
    (repro.plan.heuristics — the TBR-CIM hybrid/normal reconfiguration
    analogue): a TILE_STREAM request may fall back to LAYER_STREAM for
    aggressively-GQA geometries where generation-fusion is
    HBM-traffic-negative (DESIGN.md §2).  Full-model paths resolve this
    once via ``repro.plan.plan_model``; this per-call resolution is
    guaranteed to agree with it (tests/test_plan.py)."""
    from repro.plan.heuristics import resolve_layer_mode
    x_kv = x if x_kv is None else x_kv
    mode = resolve_layer_mode(
        mode or cfg.execution_mode, d_kv=x_kv.shape[-1],
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        attn_kind=cfg.attn_kind,
        fuse_kv_generation=cfg.fuse_kv_generation)
    window = cfg.sliding_window if cfg.attn_kind == AttnKind.SLIDING else 0

    from repro.distributed.hints import constrain
    q = jnp.einsum("bsd,dhe->bhse", x, params["wq"].astype(x.dtype))
    if cfg.use_qk_norm:
        q = ref.rms_norm(q, params["q_gamma"], eps=cfg.norm_eps)
    if sin is not None:
        q_sin, q_cos = sin, cos
        if q_offset or q.shape[2] != x_kv.shape[1]:
            # Decode/offset: q uses the tail of the tables.
            q_sin = sin[q_offset:q_offset + q.shape[2]] if sin.ndim == 2 else sin
            q_cos = cos[q_offset:q_offset + q.shape[2]] if cos.ndim == 2 else cos
        q = apply_rope_bsd(q, q_sin, q_cos)
    q = constrain(q, "attn_q")   # context-parallel hint (hillclimb lever)

    out = ops.attention_by_mode(
        mode, q, x_kv, params["wk"], params["wv"],
        sin=sin if sin is not None and sin.ndim == 2 else None,
        cos=cos if cos is not None and cos.ndim == 2 else None,
        k_gamma=params.get("k_gamma"), causal=causal, window=window,
        q_offset=q_offset, norm_eps=cfg.norm_eps, use_pallas=use_pallas)
    out = constrain(out, "attn_out")
    # M-RoPE (batch-dependent tables) can't use the fused-rope path above;
    # handled by the caller passing pre-roped K via mode dispatch fallback.
    return jnp.einsum("bhse,hed->bsd", out, params["wo"].astype(x.dtype))


def attention_forward_mrope(params: Params, cfg: ModelConfig, x: jax.Array, *,
                            sin_b, cos_b, causal: bool = True,
                            mode: Optional[ExecutionMode] = None,
                            use_pallas: bool = False) -> jax.Array:
    """qwen2-vl: batch-dependent M-RoPE tables (B, S, hd//2).  K is roped
    outside the kernel (LAYER_STREAM semantics for K-gen; TILE_STREAM still
    applies to the V path conceptually but we keep it uniform here)."""
    mode = mode or cfg.execution_mode
    q = jnp.einsum("bsd,dhe->bhse", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bhse", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bhse", x, params["wv"].astype(x.dtype))
    q = apply_rope_bsd(q, sin_b, cos_b)
    k = apply_rope_bsd(k, sin_b, cos_b)
    out = ops.multi_head_attention(q, k, v, causal=causal,
                                   use_pallas=use_pallas)
    return jnp.einsum("bhse,hed->bsd", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Decode-path attention (KV cache)
# ---------------------------------------------------------------------------

def rope_at(pos, head_dim: int, theta: float):
    """sin/cos for a dynamic position — O(hd), no table: (1, hd//2) for a
    scalar ``pos``, (B, 1, hd//2) for one position per row ``(B,)``."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if pos.ndim:
        ang = pos.astype(jnp.float32)[:, None, None] * freqs
        return jnp.sin(ang), jnp.cos(ang)
    ang = pos.astype(jnp.float32) * freqs
    return jnp.sin(ang)[None], jnp.cos(ang)[None]


def attention_decode(params: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, *, use_rope: bool = True
                     ) -> Tuple[jax.Array, Params]:
    """x: (B, 1, D) pre-normed; cache: {k: (B,Hkv,W,hd), v: ..., len}.

    ``len`` is one position for every row, shape ``()``, or one per row,
    ``(B,)`` (the engine's pooled slots of unequal K/V length): each row
    then takes its own RoPE angle, write slot and valid length, and
    attends only its own entries — positions past its length are masked
    out of the softmax over the full width W.

    Sliding-window archs allocate W = min(max_len, window) and the cache is
    a *ring buffer* (slot = pos % W) — a 0.5M-token SWA stream runs in a
    window-sized cache.  RoPE is applied at write time with the absolute
    position, so ring wrapping is transparent to attention.
    """
    pos = cache["len"]
    W = cache["k"].shape[2]
    q = jnp.einsum("bsd,dhe->bhse", x, params["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dhe->bhse", x, params["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dhe->bhse", x, params["wv"].astype(x.dtype))
    if cfg.use_qk_norm:
        q = ref.rms_norm(q, params["q_gamma"], eps=cfg.norm_eps)
        k_new = ref.rms_norm(k_new, params["k_gamma"], eps=cfg.norm_eps)
    if use_rope and cfg.head_dim:
        sin_t, cos_t = rope_at(pos, cfg.head_dim, cfg.rope_theta)
        q = apply_rope_bsd(q, sin_t, cos_t)
        k_new = apply_rope_bsd(k_new, sin_t, cos_t)
    slot = jax.lax.rem(pos, W)

    def write(c, new):
        new = new.astype(c.dtype)
        if pos.ndim:                        # each row at its own slot
            return jax.vmap(lambda r, n, i: jax.lax
                            .dynamic_update_slice_in_dim(r, n, i, 1)
                            )(c, new, slot)
        return jax.lax.dynamic_update_slice_in_dim(c, new, slot, 2)

    k_cache = write(cache["k"], k_new)
    v_cache = write(cache["v"], v_new)
    is_ring = cfg.attn_kind == AttnKind.SLIDING
    valid = jnp.minimum(pos + 1, W) if is_ring else pos + 1
    out = ref.ref_decode_attention(q, k_cache, v_cache, valid, window=0)
    o = jnp.einsum("bhse,hed->bsd", out, params["wo"].astype(x.dtype))
    return o, {"k": k_cache, "v": v_cache, "len": pos + 1}


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_model: Optional[int] = None,
             d_ff: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":
        return {"w_gate": dense_init(ks[0], (d, f), _pdtype(cfg)),
                "w_up": dense_init(ks[1], (d, f), _pdtype(cfg)),
                "w_down": dense_init(ks[2], (f, d), _pdtype(cfg))}
    return {"w_up": dense_init(ks[0], (d, f), _pdtype(cfg)),
            "w_down": dense_init(ks[1], (f, d), _pdtype(cfg))}


def mlp_forward(params: Params, cfg: ModelConfig, x: jax.Array, *,
                use_pallas: bool = False) -> jax.Array:
    if "w_gate" in params:
        g = ops.projection(x, params["w_gate"].astype(x.dtype), use_pallas=use_pallas)
        u = ops.projection(x, params["w_up"].astype(x.dtype), use_pallas=use_pallas)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(ops.projection(x, params["w_up"].astype(x.dtype),
                                       use_pallas=use_pallas))
    return ops.projection(h, params["w_down"].astype(x.dtype),
                          use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# MoE FFN — gather-based static-capacity dispatch (EP over 'model' when the
# expert count divides the axis, TP-within-expert otherwise; DESIGN.md §5)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), _pdtype(cfg), scale=0.02),
        "w_gate": dense_init(ks[1], (e, d, f), _pdtype(cfg)),
        "w_up": dense_init(ks[2], (e, d, f), _pdtype(cfg)),
        "w_down": dense_init(ks[3], (e, f, d), _pdtype(cfg)),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=f * cfg.num_shared_experts)
    return p


def moe_forward(params: Params, cfg: ModelConfig, x: jax.Array, *,
                capacity_factor: Optional[float] = None,
                use_pallas: bool = False) -> jax.Array:
    """x: (B, S, D).  Static-shape top-k routing with per-expert capacity.

    Dispatch = gather (expert_slots -> token ids), combine = scatter-add.
    No (T, E, C) one-hot tensors: memory stays O(T·E + E·C·D).
    """
    from repro.core import runtime
    from repro.distributed.hints import constrain
    if capacity_factor is None:
        capacity_factor = runtime.get("moe_capacity", 1.25)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    # Grouped dispatch (GShard groups == data shards): routing/slotting is
    # computed independently per token group, so the expert gather never
    # crosses the data axis — the dominant MoE collective disappears
    # (perf lever; groups=1 is the plain formulation).
    groups = runtime.get("moe_groups", 1)
    T_all = B * S
    if T_all % groups != 0:
        groups = 1
    Tg = T_all // groups
    xt = x.reshape(groups, Tg, D)
    cap = max(int(Tg * K / E * capacity_factor), 4)
    cap = min(pad_to(cap, 4), Tg)

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, K)               # (G, Tg, K)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)

    def slot_group(topi_g, topw_g):
        """One group's slotting: (Tg,K) -> (E,C) token ids / weights."""
        flat_e = topi_g.reshape(-1)                    # (Tg*K,)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot).max(
            axis=-1, where=onehot > 0, initial=0)
        keep = pos_in_e < cap
        slot = jnp.where(keep, flat_e * cap + pos_in_e, E * cap)
        token_of_slot = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(
            jnp.arange(Tg * K, dtype=jnp.int32) // K, mode="drop")
        slot_used = jnp.zeros((E * cap + 1,), jnp.bool_).at[slot].set(
            True, mode="drop")
        wslot = jnp.zeros((E * cap + 1,), jnp.float32).at[slot].set(
            topw_g.reshape(-1), mode="drop")
        return (token_of_slot[:E * cap].reshape(E, cap),
                slot_used[:E * cap].reshape(E, cap),
                wslot[:E * cap].reshape(E, cap))

    tok_ids, used, wslot = jax.vmap(slot_group)(topi, topw)  # (G,E,C...)

    xe = jnp.take_along_axis(
        xt[:, :, None, :].astype(x.dtype),
        tok_ids.reshape(groups, E * cap, 1, 1), axis=1
    )[:, :, 0].reshape(groups, E, cap, D)
    xe = xe * used[..., None].astype(xe.dtype)
    xe = jnp.swapaxes(xe, 0, 1)                        # (E, G, C, D)
    xe = constrain(xe, "moe_dispatch")                 # P(model, data, ...)
    g = jnp.einsum("egcd,edf->egcf", xe, params["w_gate"].astype(xe.dtype))
    u = jnp.einsum("egcd,edf->egcf", xe, params["w_up"].astype(xe.dtype))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("egcf,efd->egcd", h, params["w_down"].astype(xe.dtype))
    ye = jnp.swapaxes(ye, 0, 1)                        # (G, E, C, D)

    # combine: weight each slot by its gate and scatter-add back per group
    def combine_group(ye_g, tok_g, w_g):
        return jnp.zeros((Tg, D), jnp.float32).at[tok_g.reshape(-1)].add(
            (ye_g * w_g[..., None].astype(ye_g.dtype))
            .reshape(E * cap, D).astype(jnp.float32))

    y = jax.vmap(combine_group)(ye, tok_ids, wslot)    # (G, Tg, D)
    out = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in params:
        out = out + mlp_forward(params["shared"], cfg, x,
                                use_pallas=use_pallas)
    return out
