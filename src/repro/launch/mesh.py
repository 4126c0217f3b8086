"""Production mesh construction.

A *function*, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Single pod: 16×16 = 256 chips ('data', 'model').
Multi-pod:  2×16×16 = 512 chips ('pod', 'data', 'model') — the pod axis is
pure data parallelism; only the gradient all-reduce crosses pods.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(shape))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)}. "
            "Set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (launch/dryrun.py does this).")
    # more devices available than the mesh needs (e.g. 512 host devices,
    # single-pod mesh): use a prefix
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes)


def make_elastic_mesh(n_model: int = 0) -> Mesh:
    """Best mesh for *whatever devices survive* — the elastic-restart path.

    Used by launch/train.py on restart after device loss: model axis keeps
    the largest power-of-two that divides the device count (capped at 16),
    the rest becomes data parallelism.
    """
    n = len(jax.devices())
    if not n_model:
        n_model = 1
        while n_model < 16 and n % (n_model * 2) == 0:
            n_model *= 2
    return jax.make_mesh((n // n_model, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
