"""Production meshes. Functions, not module constants — importing this
module never touches jax device state (device count is locked at first
jax init, and only the dry-run forces 512 host devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod.

    Axes: data (DP, gradient reduction), model (TP/EP); multi-pod adds a
    leading pod axis (DP across DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (host) devices exist — tests."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_serve_mesh(n_model: int = 1):
    """(1, n_model) serve mesh over the FIRST n_model local devices.

    Unlike `jax.make_mesh` (which lays out every device), serving wants exactly
    the shard count asked for — e.g. 4 pool shards on an 8-device host —
    so the mesh is built from an explicit device subset."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n_model:
        raise ValueError(
            f"serve mesh wants {n_model} model shards but only "
            f"{len(devs)} device(s) exist")
    return Mesh(np.asarray(devs[:n_model]).reshape(1, n_model),
                ("data", "model"))
