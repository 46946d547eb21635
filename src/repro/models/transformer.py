"""Unified decoder-only transformer covering the dense / moe / ssm / hybrid /
vlm families.  Homogeneous layer stacks are ``lax.scan``-ed over stacked
params (small HLO even at 64 layers); heterogeneous prefixes (deepseek's
first dense layers) get their own stack.

Every attention layer runs through the paper's execution-mode dispatch, so
any arch can execute NON_STREAM / LAYER_STREAM / TILE_STREAM.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.scan_utils import maybe_scan
from repro.core.types import AttnKind, ExecutionMode, Family, ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import ssm as SSM

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Per-layer init/apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, moe: bool) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": L.rms_norm_init(cfg)}
    if cfg.family == Family.SSM:
        p["ssm"] = SSM.ssm_init(ks[0], cfg)
        return p
    if cfg.attn_kind == AttnKind.MLA:
        p["attn"] = MLA.mla_init(ks[0], cfg)
    elif cfg.num_heads:
        p["attn"] = L.attention_init(ks[0], cfg)
    if cfg.family == Family.HYBRID:
        p["ssm"] = SSM.ssm_init(ks[1], cfg)
        p["mix_beta"] = jnp.ones((2,), jnp.float32)
    p["norm2"] = L.rms_norm_init(cfg)
    if moe:
        p["moe"] = L.moe_init(ks[2], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[3], cfg)
    return p


def _layer_apply(p: Params, cfg: ModelConfig, x: jax.Array, *,
                 sin, cos, moe: bool,
                 mode: Optional[ExecutionMode],
                 use_pallas: bool, mrope_tabs=None) -> jax.Array:
    h = L.rms_norm(p["norm1"], x, eps=cfg.norm_eps)
    if cfg.family == Family.SSM:
        return x + SSM.ssm_forward(p["ssm"], cfg, h, use_pallas=use_pallas)

    if cfg.attn_kind == AttnKind.MLA:
        attn_out = MLA.mla_forward(p["attn"], cfg, h, sin=sin, cos=cos,
                                   causal=True, mode=mode,
                                   use_pallas=use_pallas)
    elif mrope_tabs is not None:
        attn_out = L.attention_forward_mrope(p["attn"], cfg, h,
                                             sin_b=mrope_tabs[0],
                                             cos_b=mrope_tabs[1], causal=True,
                                             mode=mode, use_pallas=use_pallas)
    else:
        attn_out = L.attention_forward(p["attn"], cfg, h, sin=sin, cos=cos,
                                       causal=True, mode=mode,
                                       use_pallas=use_pallas)
    if cfg.family == Family.HYBRID:
        ssm_out = SSM.ssm_forward(p["ssm"], cfg, h, use_pallas=use_pallas)
        beta = jax.nn.softmax(p["mix_beta"]).astype(x.dtype)
        x = x + beta[0] * attn_out + beta[1] * ssm_out
    else:
        x = x + attn_out
    h2 = L.rms_norm(p["norm2"], x, eps=cfg.norm_eps)
    if moe:
        x = x + L.moe_forward(p["moe"], cfg, h2, use_pallas=use_pallas)
    else:
        x = x + L.mlp_forward(p["mlp"], cfg, h2, use_pallas=use_pallas)
    return x


# ---------------------------------------------------------------------------
# Model init / forward
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers if cfg.family == Family.MOE else (
        cfg.num_layers if cfg.family != Family.MOE else 0)
    params: Params = {"embed": L.embed_init(ks[0], cfg),
                      "final_norm": L.rms_norm_init(cfg)}
    if cfg.family == Family.MOE:
        if cfg.first_dense_layers:
            dkeys = jax.random.split(ks[1], cfg.first_dense_layers)
            params["dense_layers"] = jax.vmap(
                lambda k: _layer_init(k, cfg, moe=False))(dkeys)
        mkeys = jax.random.split(ks[2], cfg.num_layers - cfg.first_dense_layers)
        params["layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe=True))(mkeys)
    else:
        lkeys = jax.random.split(ks[1], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe=False))(lkeys)
    if cfg.mtp_depth:
        params["mtp_proj"] = L.dense_init(ks[3], (2 * cfg.d_model, cfg.d_model),
                                          jnp.dtype(cfg.param_dtype))
    return params


def _scan_stack(stack: Params, cfg: ModelConfig, x: jax.Array, *,
                sin, cos, moe: bool, mode, use_pallas, mrope_tabs,
                remat: bool) -> jax.Array:
    from repro.core import runtime
    body = functools.partial(_layer_apply, cfg=cfg, sin=sin, cos=cos, moe=moe,
                             mode=mode, use_pallas=use_pallas,
                             mrope_tabs=mrope_tabs)
    # remat policy knob (perf lever): 'none' recomputes everything (min
    # memory); 'dots' saves matmul outputs (no matmul recompute in bwd).
    policy_name = runtime.get("remat_policy", "none")
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if policy_name == "dots" else None)

    def step(carry, lp):
        fn = jax.checkpoint(lambda c, p: body(p, x=c), policy=policy) \
            if remat else (lambda c, p: body(p, x=c))
        return fn(carry, lp), None

    x, _ = maybe_scan(step, x, stack)
    return x


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            mode: Optional[ExecutionMode] = None, use_pallas: bool = False,
            remat: bool = False) -> jax.Array:
    """batch: {"tokens": (B,S) int32 | "embeds": (B,S,D),
               "positions": (3,B,S) optional (vlm M-RoPE)}.
    Returns logits (B, S, vocab_padded) in f32."""
    x = forward_hidden(params, cfg, batch, mode=mode, use_pallas=use_pallas,
                       remat=remat)
    return L.unembed(params["embed"], x, cfg)


def forward_hidden(params: Params, cfg: ModelConfig,
                   batch: Dict[str, jax.Array], *,
                   mode: Optional[ExecutionMode] = None,
                   use_pallas: bool = False,
                   remat: bool = False) -> jax.Array:
    """forward() up to (but excluding) the unembed projection."""
    mode = mode or cfg.execution_mode
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = L.embed_lookup(params["embed"], batch["tokens"])
    S = x.shape[1]
    sin = cos = None
    mrope_tabs = None
    if cfg.family == Family.VLM and cfg.mrope_sections and "positions" in batch:
        mrope_tabs = L.mrope_tables(cfg, batch["positions"])
    elif cfg.num_heads and cfg.attn_kind != AttnKind.NONE:
        hd = (cfg.qk_rope_head_dim if cfg.attn_kind == AttnKind.MLA
              else cfg.head_dim)
        sin, cos = L.rope_tables_for(cfg, S, head_dim=hd)
    if cfg.family == Family.MOE and cfg.first_dense_layers:
        x = _scan_stack(params["dense_layers"], cfg, x, sin=sin, cos=cos,
                        moe=False, mode=mode, use_pallas=use_pallas,
                        mrope_tabs=mrope_tabs, remat=remat)
    x = _scan_stack(params["layers"], cfg, x, sin=sin, cos=cos,
                    moe=(cfg.family == Family.MOE), mode=mode,
                    use_pallas=use_pallas, mrope_tabs=mrope_tabs, remat=remat)
    return L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps)


def chunked_xent(params: Params, cfg: ModelConfig, hidden: jax.Array,
                 labels: jax.Array, *, chunk: int = 512
                 ) -> jax.Array:
    """Cross-entropy with the unembed projection computed per sequence
    chunk — the (B, S, vocab) logits tensor never materializes (vocabs here
    reach 256k; full logits would dominate training memory)."""
    B, S, D = hidden.shape
    c = min(chunk, S)
    nc = S // c if S % c == 0 else 1
    if S % c != 0:
        c = S
        nc = 1
    hc = jnp.moveaxis(hidden.reshape(B, nc, c, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)

    def chunk_loss(carry, inp):
        h, l = inp
        logits = L.unembed(params["embed"], h, cfg)
        valid = l >= 0
        l = jnp.maximum(l, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, l[..., None], axis=-1)[..., 0]
        s, n = carry
        return (s + jnp.sum(nll * valid), n + jnp.sum(valid)), None

    (loss_sum, count), _ = maybe_scan(
        chunk_loss, (jnp.zeros(()), jnp.zeros((), jnp.int32)), (hc, lc))
    return loss_sum / jnp.maximum(count, 1)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            mode: Optional[ExecutionMode] = None, use_pallas: bool = False,
            remat: bool = True) -> jax.Array:
    """Next-token cross-entropy; labels == -1 are masked."""
    hidden = forward_hidden(params, cfg, batch, mode=mode,
                            use_pallas=use_pallas, remat=remat)
    return chunked_xent(params, cfg, hidden, batch["labels"])


# ---------------------------------------------------------------------------
# Serving: prefill + decode (KV / latent / SSM-state caches per family)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    n_layers = cfg.num_layers
    if cfg.family == Family.SSM:
        one = SSM.ssm_init_cache(cfg, batch, dt)
    elif cfg.attn_kind == AttnKind.MLA:
        one = MLA.mla_init_cache(cfg, batch, max_len, dt)
    else:
        kv_len = max_len
        if cfg.attn_kind == AttnKind.SLIDING:
            kv_len = min(max_len, cfg.sliding_window)   # ring buffer
        one = {"k": jnp.zeros((batch, cfg.num_kv_heads, kv_len,
                               cfg.head_dim), dt),
               "v": jnp.zeros((batch, cfg.num_kv_heads, kv_len,
                               cfg.head_dim), dt)}
        if cfg.family == Family.HYBRID:
            ssm_c = {k: v for k, v in SSM.ssm_init_cache(cfg, batch, dt).items()
                     if k != "len"}
            one = {"attn": one, "ssm": ssm_c}
    # stack per layer; drop inner "len" counters — one global counter
    one = {k: v for k, v in one.items() if k != "len"}
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(
        a[None], (n_layers,) + a.shape), one)
    return {"layers": stacked, "len": jnp.zeros((), jnp.int32)}


def _decode_layer(p: Params, cfg: ModelConfig, x: jax.Array, cache_l: Params,
                  pos) -> Tuple[jax.Array, Params]:
    h = L.rms_norm(p["norm1"], x, eps=cfg.norm_eps)
    if cfg.family == Family.SSM:
        out, new_c = SSM.ssm_decode(p["ssm"], cfg, h,
                                    {**cache_l, "len": pos})
        new_c.pop("len")
        return x + out, new_c
    if cfg.attn_kind == AttnKind.MLA:
        out, new_c = MLA.mla_decode(p["attn"], cfg, h,
                                    {**cache_l, "len": pos})
        new_c.pop("len")
    elif cfg.family == Family.HYBRID:
        a_out, new_a = L.attention_decode(p["attn"], cfg, h,
                                          {**cache_l["attn"], "len": pos})
        s_out, new_s = SSM.ssm_decode(p["ssm"], cfg, h,
                                      {**cache_l["ssm"], "len": pos})
        beta = jax.nn.softmax(p["mix_beta"]).astype(x.dtype)
        out = beta[0] * a_out + beta[1] * s_out
        new_a.pop("len"); new_s.pop("len")
        new_c = {"attn": new_a, "ssm": new_s}
    else:
        out, new_c = L.attention_decode(p["attn"], cfg, h,
                                        {**cache_l, "len": pos})
        new_c.pop("len")
    x = x + out
    h2 = L.rms_norm(p["norm2"], x, eps=cfg.norm_eps)
    if "moe" in p:
        x = x + L.moe_forward(p["moe"], cfg, h2)
    else:
        x = x + L.mlp_forward(p["mlp"], cfg, h2)
    return x, new_c


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jax.Array) -> Tuple[jax.Array, Params]:
    """One serving step: tokens (B, 1) -> (logits (B, 1, V), new cache).

    The (layer-stacked) cache is scanned together with the layer params.
    MoE prefix layers (deepseek) share the same cache tensor layout, so we
    scan dense-prefix and moe stacks separately over cache slices.

    ``cache["len"]`` is the position every row decodes at, shape ``()``,
    or one position per row, ``(B,)``, for rows of unequal K/V length
    (plain ``{"k", "v"}`` attention caches; ``L.attention_decode``); the
    returned ``len`` keeps its shape.
    """
    x = L.embed_lookup(params["embed"], tokens)
    pos = cache["len"]

    def step(carry, inp):
        lp, lc = inp
        y, new_c = _decode_layer(lp, cfg, carry, lc, pos)
        return y, new_c

    if cfg.family == Family.MOE and cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        head = jax.tree.map(lambda a: a[:nd], cache["layers"])
        tail = jax.tree.map(lambda a: a[nd:], cache["layers"])
        x, new_head = maybe_scan(step, x, (params["dense_layers"], head))
        x, new_tail = maybe_scan(step, x, (params["layers"], tail))
        new_layers = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], 0), new_head, new_tail)
    else:
        x, new_layers = maybe_scan(step, x, (params["layers"],
                                               cache["layers"]))
    x = L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, {"layers": new_layers, "len": pos + 1}


def _ssm_prefill_state(p_ssm: Params, cfg: ModelConfig, h: jax.Array,
                       use_pallas: bool):
    """Run the SSM mixer over the prompt, returning (out, conv_state,
    final ssd state) for cache fill."""
    B, S, _ = h.shape
    d, d_inner, nheads, headdim = SSM.ssm_dims(cfg)
    proj = jnp.dot(h, p_ssm["in_proj"].astype(h.dtype))
    xs, z, b, c, dt = SSM._split_proj(cfg, proj, d_inner, nheads)
    xbc = jnp.concatenate([xs, b, c], axis=-1)
    conv_out, conv_state = SSM._causal_conv(xbc, p_ssm["conv_w"].astype(h.dtype))
    xbc_a = jax.nn.silu(conv_out)
    xs = xbc_a[..., :d_inner]
    b = xbc_a[..., d_inner:d_inner + cfg.ssm_state]
    c = xbc_a[..., d_inner + cfg.ssm_state:]
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p_ssm["dt_bias"][None, None])
    a = -jnp.exp(p_ssm["a_log"])
    xh = xs.reshape(B, S, nheads, headdim)
    from repro.kernels import ops as _ops
    y, final_state = _ops.ssd(xh, dtp, a, b, c, chunk=cfg.ssm_chunk,
                              use_pallas=use_pallas)
    y = y + xh * p_ssm["d_skip"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(B, S, d_inner)
    from repro.kernels import ref as _ref
    y = _ref.rms_norm(y * jax.nn.silu(z), p_ssm["norm_gamma"],
                      eps=cfg.norm_eps)
    out = jnp.dot(y, p_ssm["out_proj"].astype(h.dtype))
    return out, conv_state, final_state


def _project_kv(p_attn: Params, cfg: ModelConfig, h: jax.Array, sin, cos):
    k = jnp.einsum("bsd,dhe->bhse", h, p_attn["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhe->bhse", h, p_attn["wv"].astype(h.dtype))
    if cfg.use_qk_norm:
        from repro.kernels import ref as _ref
        k = _ref.rms_norm(k, p_attn["k_gamma"], eps=cfg.norm_eps)
    if sin is not None:
        k = L.apply_rope_bsd(k, sin, cos)
    return k, v


def _prefill_layer(p: Params, cfg: ModelConfig, x: jax.Array, cache_l: Params,
                   *, sin, cos, use_pallas: bool,
                   lp=None) -> Tuple[jax.Array, Params]:
    """One layer of single-pass prefill: compute the layer output AND fill
    the cache.  K/V materialize into the cache by necessity (they ARE the
    cache); the attention *compute* dispatches through the planner's
    per-layer decision when ``lp`` (an ``repro.plan.LayerPlan``) is given —
    ``kernels.ops.attention_by_plan`` with the layer's resolved mode and
    block tiling — and falls back to the flash path (LAYER_STREAM
    semantics) otherwise.  MLA keeps the latent-only cache —
    tile-streaming decompression at decode."""
    from repro.kernels import ops as _ops
    h = L.rms_norm(p["norm1"], x, eps=cfg.norm_eps)
    new_c = dict(cache_l)
    window = cfg.sliding_window if cfg.attn_kind == AttnKind.SLIDING else 0

    if cfg.family == Family.SSM:
        out, conv_state, final_state = _ssm_prefill_state(
            p["ssm"], cfg, h, use_pallas)
        new_c["conv"] = conv_state.astype(cache_l["conv"].dtype)
        new_c["state"] = final_state
        x = x + out
        return x, new_c

    if cfg.attn_kind == AttnKind.MLA:
        c_lat, k_rope = MLA._latent(p["attn"], cfg, h, sin, cos)
        new_c["c"] = jax.lax.dynamic_update_slice_in_dim(
            cache_l["c"], c_lat.astype(cache_l["c"].dtype), 0, 1)
        new_c["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
            cache_l["k_rope"], k_rope[:, 0].astype(cache_l["k_rope"].dtype),
            0, 1)
        attn_out = MLA.mla_forward(p["attn"], cfg, h, sin=sin, cos=cos,
                                   causal=True, use_pallas=use_pallas)
        x = x + attn_out
    else:
        q = jnp.einsum("bsd,dhe->bhse", h, p["attn"]["wq"].astype(h.dtype))
        if cfg.use_qk_norm:
            from repro.kernels import ref as _ref
            q = _ref.rms_norm(q, p["attn"]["q_gamma"], eps=cfg.norm_eps)
        if sin is not None:
            q = L.apply_rope_bsd(q, sin, cos)
        k, v = _project_kv(p["attn"], cfg, h, sin, cos)
        if lp is not None:
            # Planner-resolved per-layer dispatch (DESIGN.md §11): the
            # plan's mode picks the execution system (numerically
            # equivalent across modes), its blocks set the kernel tiling.
            attn_out = _ops.attention_by_plan(
                lp, q, h, p["attn"]["wk"], p["attn"]["wv"],
                sin=sin, cos=cos, k_gamma=p["attn"].get("k_gamma"),
                causal=True, window=window, norm_eps=cfg.norm_eps,
                kv=(k, v),      # cache fill already materialized them
                use_pallas=use_pallas)
        else:
            attn_out = _ops.multi_head_attention(q, k, v, causal=True,
                                                 window=window,
                                                 use_pallas=use_pallas)
        attn_out = jnp.einsum("bhse,hed->bsd", attn_out,
                              p["attn"]["wo"].astype(h.dtype))
        kv_slot = cache_l["attn"] if cfg.family == Family.HYBRID else cache_l
        filled = dict(kv_slot)
        S_in = k.shape[2]
        W = kv_slot["k"].shape[2]
        if S_in > W:
            # Ring-buffer (SWA): keep the last W keys, rolled so that
            # absolute position p lands in slot p % W.
            k = jnp.roll(k[:, :, -W:], S_in % W, axis=2)
            v = jnp.roll(v[:, :, -W:], S_in % W, axis=2)
        filled["k"] = jax.lax.dynamic_update_slice_in_dim(
            kv_slot["k"], k.astype(kv_slot["k"].dtype), 0, 2)
        filled["v"] = jax.lax.dynamic_update_slice_in_dim(
            kv_slot["v"], v.astype(kv_slot["v"].dtype), 0, 2)
        if cfg.family == Family.HYBRID:
            s_out, conv_state, final_state = _ssm_prefill_state(
                p["ssm"], cfg, h, use_pallas)
            new_ssm = dict(cache_l["ssm"])
            new_ssm["conv"] = conv_state.astype(cache_l["ssm"]["conv"].dtype)
            new_ssm["state"] = final_state
            beta = jax.nn.softmax(p["mix_beta"]).astype(x.dtype)
            x = x + beta[0] * attn_out + beta[1] * s_out
            new_c = {"attn": filled, "ssm": new_ssm}
        else:
            x = x + attn_out
            new_c = filled

    h2 = L.rms_norm(p["norm2"], x, eps=cfg.norm_eps)
    if "moe" in p:
        x = x + L.moe_forward(p["moe"], cfg, h2, use_pallas=use_pallas)
    else:
        x = x + L.mlp_forward(p["mlp"], cfg, h2, use_pallas=use_pallas)
    return x, new_c


def _dispatch_segments(cfg: ModelConfig, plan, lo: int, hi: int,
                       per_layer: bool = False):
    """Maximal runs ``[a, b)`` of model layers in the stack range
    ``[lo, hi)`` sharing one planner dispatch decision (mode + block
    tiling), each paired with a representative ``LayerPlan``.  A uniform
    (or absent) plan yields one segment — the whole stack scans in one
    ``lax.scan`` exactly as before; a heterogeneous plan splits the scan
    at mode boundaries so no layer collapses to another layer's mode.
    Plan-less layers (SSM/hybrid mixers with no attention op) carry no
    dispatch decision and merge into the surrounding segment.

    ``per_layer=True`` forces one segment per layer — used while a
    ``repro.sim.replay`` recording is active, so each layer's
    ``KernelTrace`` is emitted under *its own* op name instead of the
    segment representative's."""
    if plan is None:
        return [(lo, hi, None)]
    reps = []
    for i in range(lo, hi):
        lps = [lp for lp in plan.layers if lp.layer_index == i]
        reps.append(lps[0] if lps else None)
    if per_layer:
        return [(lo + i, lo + i + 1, reps[i]) for i in range(hi - lo)]
    def key(lp):
        return (lp.mode, lp.block_q, lp.block_kv)
    segs = []
    start = 0
    seg_rep = None                  # first attention rep in the segment
    for i in range(hi - lo):
        r = reps[i]
        if r is None:
            continue                # no dispatch decision: stay mergeable
        if seg_rep is None:
            seg_rep = r
        elif key(r) != key(seg_rep):
            segs.append((lo + start, lo + i, seg_rep))
            start, seg_rep = i, r
    segs.append((lo + start, hi, seg_rep))
    return segs


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            max_len: int, *, mode: Optional[ExecutionMode] = None,
            plan=None,
            use_pallas: bool = False) -> Tuple[jax.Array, Params]:
    """Single-pass prompt processing: fills the cache and returns
    full-prompt logits (B, S, V).

    ``plan`` — an ``repro.plan.ExecutionPlan`` for this model: each
    layer's attention dispatches under *its own* resolved mode and block
    tiling (``kernels.ops.attention_by_plan``); heterogeneous plans split
    the layer scan into maximal same-mode segments instead of collapsing
    to the first layer's mode (DESIGN.md §11).  ``mode`` is the legacy
    knob (the cache-fill path is mode-invariant; kept for API
    compatibility)."""
    del mode                                # legacy knob, see docstring
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)
    x = L.embed_lookup(params["embed"], tokens)
    sin = cos = None
    if cfg.num_heads and cfg.attn_kind != AttnKind.NONE:
        hd = (cfg.qk_rope_head_dim if cfg.attn_kind == AttnKind.MLA
              else cfg.head_dim)
        sin, cos = L.rope_tables_for(cfg, S, head_dim=hd)

    def scan_fill(x, stack, cache_slice, lp=None):
        def stp(carry, inp):
            lpar, lc = inp
            return _prefill_layer(lpar, cfg, carry, lc, sin=sin, cos=cos,
                                  use_pallas=use_pallas, lp=lp)
        return maybe_scan(stp, x, (stack, cache_slice))

    if cfg.family == Family.MOE and cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        stacks = [("dense_layers", 0, nd), ("layers", nd, cfg.num_layers)]
    else:
        stacks = [("layers", 0, cfg.num_layers)]
    # Under an active kernel recording, split per layer so each layer's
    # KernelTrace carries its own op name (recording implies the
    # unrolled path — inside lax.scan the recorder sees tracers and
    # stays silent anyway).
    import sys
    replay = sys.modules.get("repro.sim.replay")
    rec_active = (replay is not None
                  and replay.active_recorder() is not None)
    parts = []
    for pname, lo, hi in stacks:
        for a, b, lp in _dispatch_segments(cfg, plan, lo, hi,
                                           per_layer=rec_active):
            seg_p = jax.tree.map(lambda t: t[a - lo:b - lo], params[pname])
            seg_c = jax.tree.map(lambda t: t[a:b], cache["layers"])
            x, new_c = scan_fill(x, seg_p, seg_c, lp)
            parts.append(new_c)
    new_layers = parts[0] if len(parts) == 1 else jax.tree.map(
        lambda *ls: jnp.concatenate(ls, 0), *parts)

    x = L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, {"layers": new_layers,
                    "len": jnp.full((), S, jnp.int32)}
