"""Cross-pod gradient compression (int8 + error feedback).

At 2+ pods the data-parallel all-reduce crosses the DCN (an order of
magnitude slower than ICI — launch/dryrun.py models it at ICI/10).  The
standard mitigation: reduce in-pod at full precision, then exchange int8
per-tensor-scaled gradients across pods, with an error-feedback accumulator
so quantization noise is unbiased over steps (1-bit-Adam lineage).

Implemented with ``shard_map`` over the 'pod' axis; lowers to
collective-permute (pairwise exchange for 2 pods) on int8 payloads —
8x less DCN traffic than bf16/f32 all-reduce.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def cross_pod_mean_int8(grads: Any, mesh, *, axis: str = "pod") -> Any:
    """Average gradient pytree across the pod axis with int8 payloads.

    Gradients are assumed already reduced within-pod (XLA inserts the in-pod
    all-reduce from sharding propagation); this exchanges pod-halves only.
    """
    if axis not in mesh.shape or mesh.shape[axis] == 1:
        return grads

    npods = mesh.shape[axis]

    def exchange(g):
        def body(local):
            q, scale = _quantize(local.astype(jnp.float32))
            total = _dequantize(q, scale)      # own contribution, dequantized
            # ring exchange: (npods-1) hops of int8 payloads
            perm = [(i, (i + 1) % npods) for i in range(npods)]
            cur_q, cur_s = q, scale
            for _ in range(npods - 1):
                cur_q = jax.lax.ppermute(cur_q, axis, perm)
                cur_s = jax.lax.ppermute(cur_s, axis, perm)
                total = total + _dequantize(cur_q, cur_s)
            return (total / npods).astype(local.dtype)

        spec = P()  # grads replicated w.r.t. pod axis inside the shard_map
        return jax.shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=spec, check_vma=False)(g)

    return jax.tree.map(exchange, grads)


class ErrorFeedback:
    """Error-feedback state: residual = (true - quantized) accumulates and
    is re-injected next step, making int8 compression unbiased over time."""

    @staticmethod
    def init(grads: Any) -> Any:
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    @staticmethod
    def apply(grads: Any, residual: Any) -> Tuple[Any, Any]:
        """Returns (corrected_grads, quantization_error_to_carry)."""
        corrected = jax.tree.map(
            lambda g, r: g.astype(jnp.float32) + r, grads, residual)
        quantized = jax.tree.map(
            lambda c: _dequantize(*_quantize(c)), corrected)
        new_residual = jax.tree.map(lambda c, q: c - q, corrected, quantized)
        return quantized, new_residual
