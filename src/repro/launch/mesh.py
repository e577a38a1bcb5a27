"""Production mesh definitions.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — "pod" is a
second data-parallel axis over the slow inter-pod links (gradient
all-reduce crosses it once per step; the sampler shards samples over it).

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here uses ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which sharding is part of an array's type and a
plain slice of a sharded operand (``env[:, :χ]`` in
``core.dynamic_bond.fit_env``) raises ``ShardingTypeError``; the sampler's
collectives are placed by ``shard_map``, so it wants the compiler-placed
``Auto`` semantics.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module docstring)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has — for tests and examples.

    Runtime-aware callers (``launch/sample.py``) ask the session's
    :class:`repro.api.runtime.ClusterRuntime` instead —
    ``runtime.mesh(model)`` — so the mesh covers the runtime's *global*
    device view rather than assuming the local host."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def data_axis_names(mesh) -> tuple[str, ...]:
    return tuple(ax for ax in mesh.axis_names if ax != "model")


def data_parallel_size(mesh) -> int:
    out = 1
    for ax in data_axis_names(mesh):
        out *= mesh.shape[ax]
    return out
