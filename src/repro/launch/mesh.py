"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the pod
axis is the outer data-parallel axis (gradient all-reduce crosses DCI).

A FUNCTION, not a module constant, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before any jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Callers index and reshape sharded arrays outside shard_map (the
    # compressed all-reduce's per-shard results) and leave the layout to the
    # partitioner, which takes Auto axes; jax.make_mesh defaults to Explicit.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU engine)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))
