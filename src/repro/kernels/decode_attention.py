"""Batched single-query decode attention Pallas-TPU kernel (DESIGN.md §15).

One serving step advances a *bucket* of equal-shape slots at once: q is
(B, Hq, 1, hd) — one query row per slot — and K/V are the slots' cache
buffers (B, Hkv, W, hd) gathered from the paged pool
(``repro.serve.kv_cache``).  ``cache_len`` carries each row's valid
entry count (the new token's K/V already written), so ragged buckets
mask per row exactly like the oracle ``kernels.ref.ref_decode_attention``.

Grid: (batch, q_heads, kv_blocks) — kv innermost; the online-softmax
state lives in VMEM scratch persisting across kv grid steps, the same
discipline as ``flash_attention``.  GQA is handled in the K/V BlockSpec
index map.  The single query row is lane-padded to ``block_q`` rows
(TPU min tile); only row 0 is read back.  The per-row lengths ride in
as a scalar-prefetch operand (SMEM), so any batch size tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128   # TPU vector lane width; running stats are lane-replicated
BLOCK_Q = 8   # f32 min sublane tile: the 1-row query pads to 8 rows


def _decode_kernel(clen_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, window: int, bq: int, bk: int,
                   num_kv_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    clen = clen_ref[b]                                     # this row's length
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < clen                                     # ragged + seq pad
    if window > 0:
        mask &= kpos > clen - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                    # (bq, LANES)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)             # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    p = jnp.exp(s - m_new[:, :1])                          # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                        # (bq, LANES)
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
    acc = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        l_final = l_scr[:, :1]
        l_safe = jnp.where(l_final == 0.0, 1.0, l_final)   # fully-masked rows
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, *,
                     window: int = 0,
                     scale: Optional[float] = None,
                     block_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, 1, hd); k/v: (B, Hkv, W, hd); cache_len: () or (B,)
    int32 valid entries per row -> (B, Hq, 1, hd).

    Shapes are padded here (query rows to ``BLOCK_Q``, head dim to 128,
    KV length to the block size); padded keys sit beyond every row's
    ``cache_len`` and mask out, so no caller-side padding contract.
    """
    B, Hq, Sq, hd = q.shape
    if Sq != 1:
        raise ValueError(f"decode_attention is single-query (Sq == 1), "
                         f"got q shape {q.shape}")
    Hkv, W = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = hd ** -0.5
    bk = max(min(block_k, W), 1)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, BLOCK_Q - 1), (0, 0)))
    hd_pad = -(-hd // 128) * 128 - hd
    if hd_pad:
        qp = jnp.pad(qp, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
    w_pad = -(-W // bk) * bk - W
    if w_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, w_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, w_pad), (0, 0)))
    hdp = hd + hd_pad
    nkb = pl.cdiv(W + w_pad, bk)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, bq=BLOCK_Q, bk=bk,
        num_kv_blocks=nkb)

    # Index maps take the scalar-prefetch ref as a trailing argument.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, hdp),
                         lambda b, h, j, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hdp), lambda b, h, j, _: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hdp), lambda b, h, j, _: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK_Q, hdp),
                               lambda b, h, j, _: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, LANES), jnp.float32),
            pltpu.VMEM((BLOCK_Q, LANES), jnp.float32),
            pltpu.VMEM((BLOCK_Q, hdp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, BLOCK_Q, hdp), q.dtype),
        interpret=interpret,
    )(clen, qp, k, v)
    return out[:, :, :1, :hd]
