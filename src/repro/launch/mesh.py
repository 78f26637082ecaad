"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state.  Callers that need the 512-placeholder-device
view (the dry-run) must set XLA_FLAGS before any jax import — see
``launch/dryrun.py``'s first two lines.

Every mesh here has ``Auto`` axes: GSPMD propagates shardings and the
model's ``with_sharding_constraint`` hints refer to mesh axes by name
(``jax.make_mesh`` defaults to ``Explicit`` axes, which reject both).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices: Sequence[jax.Device] | None = None
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes, over ``devices`` (default: all)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1,
                    devices: Sequence[jax.Device] | None = None
                    ) -> jax.sharding.Mesh:
    """Small ("data", "model") mesh for examples, tests and single-host
    chips; ``devices`` picks a subset (e.g. ``jax.devices()[:2]``)."""
    return make_mesh((data, model), ("data", "model"), devices)
