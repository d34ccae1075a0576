"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (v5e-256) or 2x16x16 multi-pod mesh.

    Axes: ``pod`` spans the DCN link between pods (data-parallel by default,
    pipeline stages opt-in); ``data`` is batch/FSDP; ``model`` is
    tensor/expert parallel.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist — tests / CPU smoke runs."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(num_shards: int):
    """1-D ``shard`` mesh over the first ``num_shards`` devices.

    The sharded query engine (``repro.sharding``) places one region-shard's
    bucket slabs per mesh device and routes batches by (shard, bucket).
    Raises when the runtime has fewer devices than shards — a serving
    mesh never stacks two shards on one device.  Tests on a single CPU
    device that want oversubscription pass ``mesh=None`` to the router
    instead (see :func:`shard_devices`).
    """
    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(f"need {num_shards} devices for a serving mesh, "
                         f"runtime has {len(devs)} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count="
                         f"{num_shards} for host smoke runs)")
    return jax.make_mesh((num_shards,), ("shard",),
                         axis_types=(AxisType.Auto,),
                         devices=devs[:num_shards])


def shard_devices(mesh, num_shards: int) -> list:
    """Per-shard device placement: mesh devices, or round-robin on the CPU.

    With a mesh, shard ``k`` lives on ``mesh.devices.flat[k]`` (one shard
    per device, the production regime).  Without one, shards wrap onto
    ``jax.devices()``.  Wrapping more shards than devices is allowed only
    on the CPU backend, where it keeps the routing/merging code paths
    testable on a single host device; an accelerator runtime with fewer
    devices than shards raises rather than stacking shards on one chip.
    """
    if mesh is not None:
        devs = list(mesh.devices.flat)
        if len(devs) < num_shards:
            raise ValueError(f"mesh has {len(devs)} devices for "
                             f"{num_shards} shards")
        return devs[:num_shards]
    devs = jax.devices()
    if num_shards > len(devs) and devs[0].platform != "cpu":
        raise ValueError(f"{num_shards} shards need {num_shards} "
                         f"{devs[0].platform} devices, runtime has "
                         f"{len(devs)}; refusing to stack shards on one "
                         "device")
    return [devs[k % len(devs)] for k in range(num_shards)]


def data_axes(mesh) -> tuple:
    """The axes a global batch shards over (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
