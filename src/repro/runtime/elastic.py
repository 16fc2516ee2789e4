"""Elastic scaling: choose the best mesh for however many devices survive.

Checkpoints are sharding-agnostic (checkpoint/checkpointer.py), so a restart
after losing nodes only needs (1) a new mesh over the surviving devices,
(2) new shardings from the same logical-axis rules, (3) restore.  This module
picks the mesh: keep the model axis as close to the original TP degree as
still fits (TP degree must divide flattened weight dims), give the rest to
data parallelism, and drop stragglers to a power-of-two device count.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def largest_pow2_leq(n: int) -> int:
    return 1 << (n.bit_length() - 1)


def choose_mesh_shape(n_devices: int, preferred_model: int = 16,
                      min_model: int = 1) -> Tuple[int, int]:
    """(data, model) for n_devices (uses largest power of two <= n)."""
    usable = largest_pow2_leq(max(n_devices, 1))
    model = min(preferred_model, usable)
    while model > min_model and usable % model:
        model //= 2
    return usable // model, model


def make_elastic_mesh(n_devices: Optional[int] = None,
                      preferred_model: int = 16):
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    data, model = choose_mesh_shape(n, preferred_model)
    used = devs[: data * model]
    import numpy as np
    arr = np.array(used).reshape(data, model)
    from jax.sharding import Mesh
    return Mesh(arr, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
