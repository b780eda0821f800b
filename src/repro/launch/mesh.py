"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Functions (not module-level constants) so importing this module never
touches jax device state.  Single-pod: 16×16 = 256 chips (v5e pod slice);
multi-pod: 2×16×16 = 512 chips with the leading ``pod`` axis extending data
parallelism across pods (ICI within a pod, DCN across pods).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """A device mesh whose axes are all ``Auto``: sharding is propagated by
    the compiler and steered by ``with_sharding_constraint``
    (:mod:`repro.sharding.api`), which ``Explicit`` axes would refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(devices: int | None = None):
    """Small mesh over however many devices the test environment has."""
    n = devices or len(jax.devices())
    model = 1
    for cand in (4, 2):
        if n % cand == 0 and n >= cand:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))
