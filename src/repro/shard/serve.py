"""Mesh-driven serving (DESIGN.md §13).

``serve.Engine(mesh=...)`` routes prefill and decode through
``shard_map`` over a real jax mesh (``launch.mesh`` builders — the
(data, model) production grid or ``make_host_mesh()`` for tests).  All
specs are replicated (``PartitionSpec()``): the mesh carries the
execution, the *plan*-level sharding lives in ``repro.shard.partition``
— so on the 1x1 host mesh the numerics are bit-identical to the
single-chip path, which the tier-1 suite asserts.  Parameter-level
sharding specs for real multi-device meshes come from
``distributed.sharding.param_shardings`` and compose with these wrappers
unchanged (jax re-shards inputs to match the entry specs).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import PartitionSpec as P


def mesh_prefill(mod, params, cfg, batch: Dict[str, Any], *, mesh,
                 max_len: int, **kwargs):
    """Run ``mod.prefill`` under ``shard_map`` on ``mesh`` (replicated
    specs).  ``kwargs`` (``plan=`` / ``mode=``) pass through as static
    closure state, exactly as the single-chip engine passes them."""
    kw = {k: v for k, v in kwargs.items() if v is not None}

    def fn(p, toks):
        return mod.prefill(p, cfg, {"tokens": toks}, max_len=max_len, **kw)

    f = jax.shard_map(fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                      check_vma=False)
    return f(params, batch["tokens"])


def mesh_decode_fn(mod, cfg, mesh):
    """A jitted ``shard_map`` decode step: drop-in for the engine's
    ``jax.jit(decode_step)`` closure."""

    def fn(p, cache, tok):
        return mod.decode_step(p, cfg, cache, tok)

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=P(), check_vma=False))
