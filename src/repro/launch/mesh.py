"""Meshes.  FUNCTIONS, not module-level constants — importing this module
never touches jax device state (the dry-run sets the fake device count
before any jax initialization).

Every mesh is laid over ``num_devices`` chips (default: all of
``devices``, itself defaulting to ``jax.devices()``).  The "model" axis —
or, for the pipeline mesh, the "stage" x "model" group — is one row of up
to 16 chips; "data" takes the rest.  256 chips give the 16 x 16 pod,
512 multi-pod chips 2 x 16 x 16, and one 2 x 2 host of 4 chips a single
4-wide group.
"""

from __future__ import annotations

import jax

#: widest "model" (x "stage") group: one 16-chip row of a 16 x 16 pod
GROUP_WIDTH = 16


def _layout(n: int | None, devices, multi_pod: bool):
    """(pods, data, group) sizes for ``n`` devices."""
    if n is None:
        n = len(jax.devices() if devices is None else devices)
    pods = 2 if multi_pod else 1
    group = min(GROUP_WIDTH, n // pods)
    if group < 1 or n % (pods * group):
        raise ValueError(f"{n} devices do not tile {pods} pod(s) of "
                         f"{group}-wide groups")
    return pods, n // (pods * group), group


def _mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(num_devices: int | None = None, *,
                         multi_pod: bool = False, devices=None):
    """("data", "model"), or ("pod", "data", "model") multi-pod — the "pod"
    axis carries the cross-pod (DCN-class) gradient reduction.  ``devices``
    (default ``jax.devices()``) may be described ones, for a compile."""
    pods, data, model = _layout(num_devices, devices, multi_pod)
    if multi_pod:
        return _mesh((pods, data, model), ("pod", "data", "model"), devices)
    return _mesh((data, model), ("data", "model"), devices)


def make_pipeline_mesh(num_devices: int | None = None, *,
                       multi_pod: bool = False, num_stages: int = 4,
                       devices=None):
    """Mesh variant for the paper's pipelined train_step: the model group
    is factored into ("stage", "model"), group = num_stages * tp."""
    pods, data, group = _layout(num_devices, devices, multi_pod)
    if group % num_stages:
        raise ValueError(f"{num_stages} stages do not divide a "
                         f"{group}-wide group")
    tp = group // num_stages
    if multi_pod:
        return _mesh((pods, data, num_stages, tp),
                     ("pod", "data", "stage", "model"), devices)
    return _mesh((data, num_stages, tp), ("data", "stage", "model"), devices)


def data_axes(mesh) -> tuple:
    """The batch-sharding axes for this mesh ('pod' folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_tag(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
