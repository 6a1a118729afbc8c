"""Production mesh builders.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """Mesh over the visible devices (the chips of one host, or forced
    host-platform CPU devices in tests).

    Default: all visible devices, split ``(data=n//model, model)``.
    With ``data=``: a submesh over the FIRST ``data * model`` devices —
    how a scaling sweep runs the same job at 1, 2, 4, ... data shards
    inside one process without re-initializing jax.  Either way the
    axes are ``Auto``: the engine's step is a ``shard_map`` over the
    data axes, and its reduce update is partitioned by XLA.
    """
    devs = jax.devices()
    n = len(devs)
    if model < 1 or (data is None and n % model != 0):
        raise ValueError(
            f"make_host_mesh(model={model}, data={data}): {n} visible "
            f"device(s) cannot form a (data={n}//{max(model, 1)}, "
            f"model={model}) mesh — device count must be a positive "
            f"multiple of `model`")
    data = n // model if data is None else int(data)
    want = data * model
    if data < 1 or want > n:
        raise ValueError(
            f"make_host_mesh(model={model}, data={data}): requested a "
            f"(data={data}, model={model}) mesh = {want} device(s) but "
            f"only {n} visible")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=devs[:want])


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def data_size(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
