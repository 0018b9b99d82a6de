"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any JAX initialization; tests and
benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax


def mesh_kwargs(n_axes: int) -> dict:
    """``jax.make_mesh`` keyword arguments: every axis in automatic
    (not explicit) sharding mode."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod" axis.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The "pod" axis composes with "data" for FSDP/DP (gradients cross the slower
    inter-pod links exactly once per step); "model" carries TP/EP/SP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_kwargs(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return jax.make_mesh((n // mp, mp), ("data", "model"), **mesh_kwargs(2))
