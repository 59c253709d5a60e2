"""Activation sharding constraints that are no-ops outside a mesh context.

Model code calls constrain(x, "batch", None, "model", ...) with LOGICAL axis
names; under `with mesh:` they resolve to the mesh's physical axes ("batch"
-> ("pod", "data") as available, "model" -> "model") and emit
with_sharding_constraint; on a single host device (smoke tests, benchmarks)
they vanish. Dims whose size is not divisible by the resolved axes are
silently left unsharded — the same fallback philosophy as shardingx.policy.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


def _mesh_axes() -> dict:
    # `with mesh:` sets the legacy thread-resources context (what
    # with_sharding_constraint's spec-only form consumes); `jax.set_mesh`
    # sets the abstract mesh. jax exposes the former only privately.
    pm = mesh_lib.thread_resources.env.physical_mesh
    if not pm.empty:
        return dict(zip(pm.axis_names, pm.devices.shape))
    m = jax.sharding.get_abstract_mesh()
    return dict(zip(m.axis_names, m.axis_sizes))


import contextlib

# Federated tracing context: the silo mesh axis carries the vmapped silo
# dim, so logical "batch" must NOT resolve onto it (otherwise GSPMD moves
# per-silo activations across the silo boundary — measured as spurious
# cross-pod traffic in the fed local step).
_SILO_AXIS: list = [None]


@contextlib.contextmanager
def silo_context(axis: str):
    _SILO_AXIS.append(axis)
    try:
        yield
    finally:
        _SILO_AXIS.pop()


def resolve_axis(logical: Axis, sizes: dict) -> Tuple[str, ...]:
    if logical is None:
        return ()
    excluded = _SILO_AXIS[-1]
    if logical == "batch":
        return tuple(a for a in ("pod", "data")
                     if sizes.get(a, 1) > 1 and a != excluded)
    if isinstance(logical, str):
        return (logical,) if sizes.get(logical, 1) > 1 and logical != excluded else ()
    return tuple(a for a in logical if sizes.get(a, 1) > 1 and a != excluded)


def constrain(x, *logical: Axis):
    """x with a sharding constraint following the logical spec; identity when
    no mesh is active or the spec fully degenerates."""
    sizes = _mesh_axes()
    if not sizes:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    entries = []
    any_sharded = False
    for dim, name in zip(x.shape, logical):
        axes = resolve_axis(name, sizes)
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if axes and dim % prod == 0 and dim >= prod:
            entries.append(axes if len(axes) > 1 else axes[0])
            any_sharded = True
        else:
            entries.append(None)
    if not any_sharded:
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))
