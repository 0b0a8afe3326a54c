"""Production meshes and tpu-let sub-mesh carving.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {shape} mesh, have {len(devices)}; "
            "run under launch/dryrun.py which forces "
            "--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(devices):
    """A (1, n) ("data", "model") mesh: tensor parallel over ``devices``.

    One device gives a (1, 1) mesh, on which every sharding rule of
    launch/sharding.py resolves to replication on that device.
    """
    return jax.make_mesh((1, len(devices)), ("data", "model"),
                         devices=devices, axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh ('pod' included if present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def make_submesh(n_chips: int, *, model_axis: int = 16):
    """A tpu-let: a sub-mesh of ``n_chips`` chips (data x model).

    Used by the tpu-let scheduler integration (core/tpulets.py) to derive
    roofline terms for fractional partitions of a pod.  ``n_chips`` must be a
    multiple of ``model_axis`` (contiguous rectangle constraint).
    """
    assert n_chips % model_axis == 0, (n_chips, model_axis)
    devices = jax.devices()[:n_chips]
    return jax.make_mesh((n_chips // model_axis, model_axis),
                         ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
