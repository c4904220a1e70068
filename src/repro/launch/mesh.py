"""Production mesh factory.  A FUNCTION (never a module-level constant) so
importing this module never touches jax device state.

Every mesh gets Auto axes: since JAX 0.9 ``jax.make_mesh`` defaults to
Explicit axes, under which ``with_sharding_constraint`` (the ``Dist``
activation constraints) and the Pallas kernels' vmapped shard launches are
rejected.  The sharded serving path relies on GSPMD propagation, i.e. Auto."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic reshapes, tests)."""
    return _auto_mesh(shape, axes)


def make_local_mesh(tp: int = 1):
    """Local mesh with the production axis names — lets smoke tests run
    the exact sharded code path on CPU.  ``tp`` > 1 puts that many local
    devices on the "model" axis (pair with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fake them);
    the default stays the historical 1-device (1, 1) mesh."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > jax.device_count():
        raise ValueError(
            f"tp={tp} needs more devices than the {jax.device_count()} "
            "available (set --xla_force_host_platform_device_count)")
    return _auto_mesh((1, tp), ("data", "model"))
