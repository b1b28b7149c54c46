"""Production mesh construction (assignment contract).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. Single-pod: 16x16
(data, model) = 256 chips. Multi-pod: 2x16x16 (pod, data, model) = 512
chips — the ``pod`` axis carries data parallelism across the inter-pod
DCI (gradient all-reduce crosses pods; TP/EP stay inside a pod on ICI).
"""
from __future__ import annotations

import jax

from ..distributed.sharding import ShardingPolicy


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def parse_mesh(spec: str) -> dict:
    """``"data=1,model=4"`` -> ``{"data": 1, "model": 4}`` (axis order
    kept: it is the mesh's)."""
    sizes = {}
    for part in spec.split(","):
        name, _, n = part.partition("=")
        if not name.strip() or not n.strip().isdigit() or int(n) < 1:
            raise ValueError(f"mesh axis {part!r} is not <name>=<size>; "
                             "e.g. data=1,model=4")
        sizes[name.strip()] = int(n)
    return sizes


def make_mesh(sizes: dict, devices=None):
    """A mesh of ``sizes`` (``{axis: size}``, in axis order) over the
    first ``prod(sizes)`` of ``devices`` (default: JAX's devices)."""
    n = 1
    for size in sizes.values():
        n *= size
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise ValueError(f"mesh {sizes} needs {n} devices; "
                         f"{len(devices)} are there")
    return jax.make_mesh(
        tuple(sizes.values()), tuple(sizes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(sizes),
        devices=devices[:n])


def arch_sharding_policy(cfg, mesh) -> ShardingPolicy:
    """How a job of ``cfg`` is laid out on ``mesh``: the batch over the
    data (and pod) axes, and ZeRO-3 parameter sharding over them only
    for models above 8 B parameters."""
    axes = ("data", "pod") if "pod" in mesh.axis_names else ("data",)
    fsdp = cfg.param_count() > 8e9     # ZeRO-3 for everything sizable
    return ShardingPolicy(fsdp=fsdp, fsdp_axes=axes,
                          batch_axes=tuple(a for a in ("pod", "data")
                                           if a in mesh.axis_names))
