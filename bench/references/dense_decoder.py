"""Plain float32 reference of the dense decoder that the benchmark serves,
and the seeded weights both sides share.

It follows the published pre-norm GQA decoder with rotary positions (Llama
form), with the departures this repository's ``transformer`` makes and the
configuration files record under ``assumed``: RMSNorm for every norm, a
two-matrix GELU (tanh) MLP, no biases, no sliding window.  It imports
nothing of the program.

* ``make_params`` draws the weights from a key in one jitted call, on the
  device, in the served dtype and in the program's parameter layout, so
  the same arrays go to the engine and, upcast, to this reference.
* ``gaps`` runs one sequence through every layer in float32 at the
  ``highest`` matmul precision (scanned layer by layer, so only one
  layer's weights are upcast at a time) and reads, at given positions,
  how far the logit of a given token lies below the best logit.
* ``control_gaps`` runs the same sequence a second time with the operands
  of every product the program computes in bfloat16 (projections, unembed,
  and both attention products, so the K/V cache too) rounded through
  float8 (e4m3, scaled per row and per output channel), the precision
  below the configuration's bfloat16, and reads the gap of the token that
  this lower precision ranks first.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8_e4m3fn


def _static(sizes: Dict):
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
            "dtype")
    return tuple((k, sizes[k]) for k in keys)


def _padded_vocab(v: int) -> int:
    return -(-v // 128) * 128


@functools.partial(jax.jit, static_argnums=0)
def _make(static, key):
    s = dict(static)
    L, d, f = s["num_layers"], s["d_model"], s["d_ff"]
    hq, hkv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    vp = _padded_vocab(s["vocab_size"])
    dt = jnp.dtype(s["dtype"])
    ks = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(scale, dt)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                              jnp.float32)).astype(dt)

    # Embeddings of unit scale and residual branches scaled by (2L)^-1/2
    # (GPT-2's rule) keep each position's token in the residual stream.
    # With 0.02 embeddings and unscaled branches the layers wash it out:
    # every position of a request reaches one state, greedy decoding
    # repeats one token, and a whole request flips or agrees as one, so
    # the float8 control read 0 on two of three seeds.
    res = (2 * L) ** -0.5
    return {
        "embed": {"embedding": normal((vp, d), 1.0),
                  "unembed": normal((d, vp), d ** -0.5)},
        "final_norm": {"gamma": gain((d,))},
        "layers": {
            "norm1": {"gamma": gain((L, d))},
            "attn": {"wq": normal((L, d, hq, hd), d ** -0.5),
                     "wk": normal((L, d, hkv, hd), d ** -0.5),
                     "wv": normal((L, d, hkv, hd), d ** -0.5),
                     "wo": normal((L, hq, hd, d), res * (hq * hd) ** -0.5)},
            "norm2": {"gamma": gain((L, d))},
            "mlp": {"w_up": normal((L, d, f), d ** -0.5),
                    "w_down": normal((L, f, d), res * f ** -0.5)},
        },
    }


def make_params(sizes: Dict, key) -> Dict:
    """Seeded weights in the served dtype, made on the device."""
    return _make(_static(sizes), key)


def _fp8(x, axis):
    """Round ``x`` through float8 e4m3, scaled so that each slice along
    ``axis`` spans the format's range."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _proj(eq, x, w, low):
    """``einsum(eq, x, w)`` in float32, contracting the last axis of ``x``
    with the first of ``w``; with ``low`` both operands are first rounded
    through float8 (activations per row, weights per output channel)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if low:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _rms(x, gamma, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gamma.astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding, rotate-half form: x (H, S, hd)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None], jnp.cos(ang)[None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hidden(s, params, tokens, low):
    """Final-norm hidden states (S, d) of one sequence."""
    hq, hkv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    eps, S = s["norm_eps"], tokens.shape[0]
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["norm1"]["gamma"], eps)
        q = _rope(_proj("sd,dhe->hse", h, p["attn"]["wq"], low),
                  s["rope_theta"])
        k = _rope(_proj("sd,dhe->hse", h, p["attn"]["wk"], low),
                  s["rope_theta"])
        v = _proj("sd,dhe->hse", h, p["attn"]["wv"], low)
        if low:       # both attention products' operands, as a float8
            q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -2)  # K/V cache
        qg = q.reshape(hkv, hq // hkv, S, hd)
        sc = jnp.einsum("kgqe,kse->kgqs", qg, k,
                        precision=HIGHEST) * hd ** -0.5
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if low:
            pr = _fp8(pr, -1)
        o = jnp.einsum("kgqs,kse->kgqe", pr, v, precision=HIGHEST)
        o = jnp.moveaxis(o.reshape(hq, S, hd), 0, 1).reshape(S, hq * hd)
        x = x + _proj("sk,kd->sd", o, p["attn"]["wo"].reshape(hq * hd, -1),
                      low)
        h2 = _rms(x, p["norm2"]["gamma"], eps)
        up = jax.nn.gelu(_proj("sd,df->sf", h2, p["mlp"]["w_up"], low))
        return x + _proj("sf,fd->sd", up, p["mlp"]["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"]["gamma"], eps)


def _logits(s, params, h, low):
    """(rows, vocab) float32 logits, the unembed taken in vocab blocks so
    that its float32 copy never exists whole."""
    w = params["embed"]["unembed"]
    d, vp = w.shape
    nb = 8 if vp % (8 * 128) == 0 else 1
    blocks = jnp.moveaxis(w.reshape(d, nb, vp // nb), 1, 0)
    out = jax.lax.map(lambda wb: _proj("td,dv->tv", h, wb, low), blocks)
    out = jnp.moveaxis(out, 0, 1).reshape(h.shape[0], vp)
    return out[:, :s["vocab_size"]]


@functools.partial(jax.jit, static_argnums=0)
def _gaps(static, params, tokens, pos, targets, valid):
    s = dict(static)
    ref = _logits(s, params, _hidden(s, params, tokens, False)[pos], False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    return jnp.where(valid, best - got, 0.0)


@functools.partial(jax.jit, static_argnums=0)
def _control_gaps(static, params, tokens, pos, valid):
    s = dict(static)
    ref = _logits(s, params, _hidden(s, params, tokens, False)[pos], False)
    low = _logits(s, params, _hidden(s, params, tokens, True)[pos], True)
    pick = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.where(valid, best - got, 0.0)


def gaps(sizes: Dict, params, tokens, pos, targets, valid):
    """Per position ``pos[i]`` of the sequence ``tokens``: the reference's
    best logit minus its logit of ``targets[i]`` (0 where not ``valid``)."""
    return _gaps(_static(sizes), params, tokens, pos, targets, valid)


def control_gaps(sizes: Dict, params, tokens, pos, valid):
    """The same gap for the token that float8 computation ranks first."""
    return _control_gaps(_static(sizes), params, tokens, pos, valid)
