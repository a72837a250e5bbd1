"""Production mesh builders (a FUNCTION, not a module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    size = 1
    for s in shape:
        size *= s
    devices = jax.devices()
    if len(devices) > size:          # e.g. 512 virtual devices, 256-chip pod
        devices = devices[:size]
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes)


def make_abstract_mesh(shape, axes):
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` are the manual axes (all if None)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


def make_host_mesh(model: int = 1):
    """The local devices as a ("data", "model") mesh with ``model`` devices
    on the model axis.  Its axes are Auto: the sharding rules place arrays
    and the partitioner places the rest."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices do not split into a model axis of "
                         f"{model}")
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
