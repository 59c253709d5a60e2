"""Production mesh construction.

make_production_mesh is a FUNCTION (not a module constant) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.

Meshes (prescribed):
  single-pod : (16, 16)    axes ("data", "model")   = 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

FedDCL mapping (DESIGN.md §5, §7): in federated mode the silo axis is "pod"
on the multi-pod mesh (d = 2 DC-server groups, one per pod — cross-pod
traffic only at round boundaries, riding the scarce DCI exactly as the
paper's topology intends) and "data" on the single-pod mesh (d = 16 groups
of one 16-chip model-parallel row each). The compiled tabular engine
(core.federated sharded plans) spans its silo dim over BOTH silo-capable
axes jointly — see `silo_axes`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: Optional[int] = None):
    """Small ("data", "model") mesh over the actually-available devices
    (tests, examples).

    `model` must divide the device count; `data` defaults to the LARGEST
    count such that data × model devices exist (n // model), so e.g. 6
    devices with model=2 give a 3×2 mesh over the first 6 devices. An
    explicit `data` whose product exceeds the device count raises
    immediately with the device count named — the old `data * model <= n`
    assert admitted shapes like data=1, model=4 on 6 devices, which only
    failed later and opaquely inside mesh consumers.
    """
    n = jax.device_count()
    if model < 1 or n // model < 1:
        raise ValueError(
            f"make_host_mesh: model={model} needs at least {model} devices, "
            f"but only {n} are available")
    if data is None:
        data = n // model
    if data < 1 or data * model > n:
        raise ValueError(
            f"make_host_mesh: requested {data}×{model} mesh needs "
            f"{data * model} devices, but only {n} are available "
            f"(largest valid data for model={model} is {n // model})")
    devices = np.asarray(jax.devices()[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(devices, ("data", "model"))


def silo_axis_name(mesh) -> str:
    return "pod" if "pod" in mesh.axis_names else "data"


def silo_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes the compiled engine shards the silo dim over —
    ("pod", "data") jointly when both exist (hierarchical aggregation:
    intra-pod psum first, cross-pod second), else the first axis."""
    from repro.core.federated import default_silo_axes
    return default_silo_axes(mesh)


def num_silos(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes[silo_axis_name(mesh)]
