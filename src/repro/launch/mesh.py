"""Production mesh construction.

Single pod : (16, 16)    → ("data", "model")         = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) → ("pod", "data", "model")  = 512 chips

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the sharding rules and with_sharding_constraint calls name
    # mesh axes, which jax.make_mesh's default Explicit axes refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        # squarest 2-D factorization of n
        a = int(np.floor(np.sqrt(n)))
        while n % a:
            a -= 1
        shape = (a, n // a)
    return _auto_mesh(shape, axes)
