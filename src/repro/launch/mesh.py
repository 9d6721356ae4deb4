"""Mesh builders: every mesh in the repo comes from ``make_mesh``.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and only then calls make_production_mesh().
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """A mesh with Auto axis types. ``jax.make_mesh`` alone gives Explicit
    axes, under which GSPMD no longer propagates shardings through the
    engine's attention einsums (they fail as illegally sharded). Pass
    ``devices`` to build over a described topology's devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(model: int = 1, data: int = 0):
    """Small mesh over however many host devices exist (tests/examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = data or n // model
    return make_mesh((data, model), ("data", "model"))
