"""Mesh construction shared by the executors and launchers.

Written against the installed JAX (0.9), where ``jax.make_mesh`` takes
explicit axis types; the executors call ``jax.shard_map`` and
``lax.pcast`` directly.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_shapes),
                         devices=devices)
