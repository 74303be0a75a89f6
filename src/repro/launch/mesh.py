"""Production mesh construction. A function (not a module-level constant) so
importing this module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips; the "pod"
    axis carries data parallelism across the pod-interconnect (DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device (smoke tests, examples)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    # Auto axes: the model's with_sharding_constraint hints steer GSPMD
    # (``jax.make_mesh`` defaults to Explicit axes, which refuse them)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
