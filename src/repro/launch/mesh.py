"""Mesh construction and axis-rule derivation.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.layers import AxisRules


def compat_make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (its default is Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_host_mesh(data: int = 0, model: int = 1):
    """Mesh over whatever devices exist (tests / examples / smoke runs)."""
    n = len(jax.devices())
    data = data or max(1, n // model)
    return compat_make_mesh((data, model), ("data", "model"))


def make_sweep_mesh(n_devices: int = 0):
    """1-D ``('cells',)`` mesh for the sharded sweep launcher
    (launch/sweep.py): experiment batches shard along one axis — topology
    cells or candidate lanes — so the mesh is flat over however many
    (host) devices exist, or the first ``n_devices`` of them."""
    import numpy as np

    devs = jax.devices()
    if n_devices:
        devs = devs[:int(n_devices)]
    return jax.sharding.Mesh(np.array(devs), ("cells",))


def rules_for(cfg, mesh) -> AxisRules:
    """Derive AxisRules from an arch config and a mesh (DESIGN.md §4)."""
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    has_pod = "pod" in names
    dp = ("pod", "data") if has_pod else ("data",)
    if cfg is not None and cfg.pod_param_sharding == "fsdp" and has_pod:
        fsdp = ("pod", "data")
    else:
        fsdp = ("data",)
    return AxisRules(dp=dp, fsdp=fsdp, tp="model", ep=fsdp,
                     kv_seq="model", sizes=sizes)
