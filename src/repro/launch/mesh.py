"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — smoke tests see 1 device; only dryrun.py
sets ``xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod
    axis composes with data for batch/FSDP sharding, and is the boundary
    where gradient compression / hierarchical gateways attach."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_sweep_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ``('config',)`` mesh over local devices for sharded grid sweeps
    (``Scenario(mesh=...)`` / the legacy ``sweep_grid(mesh=...)``). The
    sweep shards the flat config axis of a ``ConfigGrid`` across every
    mesh device; the grid is embarrassingly parallel, so any device count
    works (the config axis is padded up to a multiple of it). With a
    user-blocked scenario (``Scenario(user_block=...)``) the rows are
    balancer-replica blocks, so the same mesh also shards the user axis:
    a 10^6-user config becomes ~10^3 block rows spread over the devices,
    per-user state and all."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    return jax.make_mesh((n,), ("config",), axis_types=(AxisType.Auto,),
                         devices=devices[:n])


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Mesh over however many local devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:data * model])
