"""Compute/communication overlap primitives (distributed-optimization
tricks, DESIGN.md §5).

``ring_collective_matmul`` — the classic all-gather↔matmul overlap: instead
of all-gathering the sharded operand and then multiplying (serializing DCN/
ICI behind the MXU), each step multiplies the *resident* shard while
``ppermute`` streams the next shard around the ring.  XLA's latency-hiding
scheduler overlaps the permute with the dot, hiding (g-1)/g of the
collective time.  This is the paper's ping-pong compute-rewriting pipeline
at the *inter-chip* level: 'rewriting' = the neighbor shard DMA, 'compute'
= the local partial matmul.

Used with shard_map over the axis that shards the contracting/gathered dim.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ring_collective_matmul(x_shard: jax.Array, w: jax.Array, *,
                           axis: str) -> jax.Array:
    """Inside shard_map: x_shard (M/g, K) is this device's row-shard of x;
    w (K, N) is resident.  Computes the full (M, N) = all_gather(x) @ w with
    the gather pipelined behind the per-shard matmuls.

    Equivalent to ``all_gather(x_shard, axis) @ w`` (tests assert it).
    """
    g = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = x_shard.shape[0]
    out = jnp.zeros((g * m, w.shape[1]), w.dtype)
    perm = [(i, (i + 1) % g) for i in range(g)]

    def step(i, carry):
        out, shard = carry
        # position of `shard` in the logical (gathered) order
        src = jax.lax.rem(idx - i + g, g)
        part = jnp.dot(shard, w, preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, part.astype(out.dtype), src * m, 0)
        # stream the next shard while (scheduler permitting) the next
        # iteration's dot runs — the inter-chip ping-pong
        shard = jax.lax.ppermute(shard, axis, perm)
        return out, shard

    out, _ = jax.lax.fori_loop(0, g, step, (out, x_shard))
    return out


def gather_matmul_overlapped(x: jax.Array, w: jax.Array, mesh, *,
                             axis: str = "model") -> jax.Array:
    """jit-level wrapper: x (M, K) sharded on dim0 over ``axis``; w
    replicated.  Returns the full product with ring overlap."""
    fn = functools.partial(ring_collective_matmul, axis=axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None), check_vma=False)(x, w)


def microbatch_overlap_note() -> str:
    """The gradient-accumulation scan in train/steps.py provides the
    batch-level overlap: microbatch i+1's forward issues while microbatch
    i's gradient all-reduce is in flight (XLA schedules the collectives of
    the scanned body asynchronously).  This function exists for
    documentation discoverability."""
    return "see train/steps.py make_train_step(microbatches=...)"
