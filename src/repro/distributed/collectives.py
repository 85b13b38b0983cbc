"""Mesh helpers for the distributed solver.

The IRLS solver is 1-D domain-decomposed exactly like the paper's MPI layout
(§3.3: one block row per process).  The production meshes are 2-D/3-D
(data, model[, pod]); the solver flattens them into a single "shard" axis.

``psum_dots`` builds the cross-shard inner products that turn the CORE PCG
variants (core/pcg.py ``pcg_masked`` / ``pcg_fixed_iters``) into the
distributed solver — the sharded backend runs the same iteration core as
host/scanned, just with psum reductions plugged in.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.pcg import vdot

SOLVER_AXIS = "shard"


def psum_dots(axis: str = SOLVER_AXIS, local_dot=None):
    """``(dot, dot2)`` inner-product closures reduced across ``axis``.

    ``dot(a, b)`` is one scalar all-reduce.  ``dot2(r, z) → (r·z, r·r)``
    fuses the CG recurrence scalar AND the squared-norm convergence test
    into ONE all-reduce of a stacked pair — that fusion is why the masked
    (early-exit) PCG costs zero collectives per step over the fixed
    schedule (which psums ``r·z`` anyway).  Because every shard receives
    the identical reduced values, any stopping decision computed from them
    (the masked ``while_loop`` cond) is taken by all shards in the same
    step — the distributed early exit needs no extra agreement round.

    ``local_dot`` masks shard-local padding (the halo plan passes
    ``vdot(a·valid, b·valid)``); plain ``vdot`` when None.
    """
    if local_dot is None:
        local_dot = vdot

    def dot(a, b):
        return jax.lax.psum(local_dot(a, b), axis)

    def dot2(r, z):
        rz_rr = jax.lax.psum(jnp.stack([local_dot(r, z),
                                        local_dot(r, r)]), axis)
        return rz_rr[0], rz_rr[1]

    return dot, dot2


def flat_mesh(devices=None) -> Mesh:
    """1-D mesh over all (given) devices with axis name 'shard'."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices).reshape(-1), (SOLVER_AXIS,))


def flatten_mesh(mesh: Mesh) -> Mesh:
    """Reshape any mesh into the solver's 1-D layout (same device order)."""
    return Mesh(mesh.devices.reshape(-1), (SOLVER_AXIS,))


def shard_leading(mesh: Mesh):
    return NamedSharding(mesh, P(SOLVER_AXIS))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
