"""Production mesh definitions.

A function, not a module-level constant: importing this module never
touches jax device state (device count locks on first jax init).

Every mesh here has Auto axes: the sharding rules in
``distributed/sharding.py`` place activations with
``with_sharding_constraint``, which only Auto axes accept
(``jax.make_mesh`` defaults to Explicit axes).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Mesh of ``shape`` over ``axes`` (Auto axis types) on
    ``jax.devices()``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist on this host (smoke tests: 1 CPU)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
