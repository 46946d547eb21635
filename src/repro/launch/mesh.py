"""Production mesh builders.  Functions, not module-level constants — merely
importing this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (16 data, 16 model).  Multi-pod: 2 pods
    (DCN axis) x the same in-pod layout = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh (CPU tests/examples) with the same axis names."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_local_mesh():
    """(data, model) over the devices present: each host's chips on
    'model' (tensor parallel over the host's interconnect), hosts on
    'data'.  One device gives the 1x1 host mesh."""
    return jax.make_mesh((jax.process_count(), jax.local_device_count()),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
