"""Production mesh construction.

Target: TPU v5e pods — 256 chips per pod in a 16×16 (data, model) layout;
the multi-pod configuration spans 2 pods = 512 chips with a leading "pod"
axis used as an outer data/context-parallel dimension (DCN-connected).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over however many devices the host actually has (tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
