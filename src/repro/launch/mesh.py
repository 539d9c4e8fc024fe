"""Mesh construction.  ``make_production_mesh`` is the spec-mandated entry point;
``make_hecaton_mesh`` refactors the same devices into the paper's 2D grid
(model axis 16 -> 4x4), and ``make_mesh_for`` dispatches on strategy.

Everything is a function — importing this module never touches jax device state.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_hecaton_mesh(*, multi_pod: bool = False, data: int = 16, mx: int = 4,
                      my: int = 4, pods: int = 2, devices=None):
    """Same chips as the production mesh; model axis factored into (mx, my).

    The (mx, my) grid is the paper's sqrt(N) x sqrt(N) die array; on a TPU v5e
    pod the ICI torus gives every row/column the ring the paper builds from
    bypass links.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    if multi_pod:
        shape = (pods, data, mx, my)
        axes = ("pod", "data", "mx", "my")
    else:
        shape = (data, mx, my)
        axes = ("data", "mx", "my")
    need = int(np.prod(shape))
    assert devices.size >= need, f"need {need} devices, have {devices.size}"
    return Mesh(devices[:need].reshape(shape), axes)


def make_mesh_for(strategy: str, *, multi_pod: bool = False, data: int = 16,
                  model: int = 16, mx: int = 4, my: int = 4, devices=None):
    if strategy == "hecaton":
        return make_hecaton_mesh(multi_pod=multi_pod, data=data, mx=mx, my=my,
                                 devices=devices)
    if devices is None:
        return make_production_mesh(multi_pod=multi_pod)
    devices = np.asarray(devices)
    if multi_pod:
        return Mesh(devices[:2 * data * model].reshape(2, data, model),
                    ("pod", "data", "model"))
    return Mesh(devices[:data * model].reshape(data, model), ("data", "model"))


def make_small_mesh(strategy: str, data: int, mx: int, my: int,
                    pods: int = 1, devices=None):
    """Mesh over the first ``pods*data*mx*my`` of ``devices`` (default
    ``jax.devices()``), for tests, weak-scaling studies and one TPU host.

    The device order follows the TPU's physical interconnect (ICI torus):
    :func:`_grid_devices` lays the hecaton (mx, my) grid on the chips'
    (x, y) coordinates where the slice is exactly that grid, so every ring
    over ``mx`` or ``my`` is a ring of neighbouring chips; otherwise
    ``mesh_utils.create_device_mesh`` orders them.  Devices without
    coordinates (CPU) keep enumeration order.

    ``pods > 1`` prepends a leading ``"pod"`` axis — the inter-package tier.
    Whether that axis is extra data parallelism or 1F1B pipeline stages is
    the *config's* call (``ParallelConfig.pod_axis_role``); the mesh only
    fixes the placement: pods are contiguous device blocks, so every
    intra-pod ring stays within a package and only stage-boundary (or
    batch-gradient) traffic crosses the slow tier.
    """
    n = pods * data * mx * my
    devs = list(devices if devices is not None else jax.devices())[:n]
    if strategy == "hecaton":
        shape, axes = (data, mx, my), ("data", "mx", "my")
    else:
        shape, axes = (data, mx * my), ("data", "model")
    if pods > 1:
        shape, axes = (pods,) + shape, ("pod",) + axes
    grid = _grid_devices(devs, shape) if strategy == "hecaton" else None
    if grid is None:
        grid = mesh_utils.create_device_mesh(shape, devices=devs)
    return Mesh(grid, axes)


def _grid_devices(devs, shape):
    """``devs`` as an array of ``shape`` whose last two axes are the chips'
    physical x and y, or None unless they span exactly that x-y plane.

    ``create_device_mesh`` lays a v5e 2x2 host out as one 4-chip ring
    folded in two, which puts one axis of a 2x2 grid on the diagonals."""
    coords = [getattr(d, "coords", None) for d in devs]
    if any(c is None for c in coords):
        return None
    nx = 1 + max(c[0] for c in coords)
    ny = 1 + max(c[1] for c in coords)
    if (nx, ny) != tuple(shape[-2:]) or len(devs) != np.prod(shape):
        return None
    order = sorted(devs, key=lambda d: (getattr(d, "core_on_chip", 0),
                                        d.coords[2], d.coords[0], d.coords[1]))
    return np.asarray(order).reshape(shape)


def pod_submeshes(mesh: Mesh):
    """Split a multi-pod mesh into one single-pod Mesh per pod-axis index.

    Pipeline stages (parallel/pipeline.py) run each stage on its pod's
    sub-mesh: inside a stage the world looks exactly like a single-pod
    mesh, so the hecaton/megatron collectives, the overlap lattice and the
    seq residual compose unchanged.  The pod order of this list defines the
    stage order (stage ``s`` sends its boundary activation to ``s+1``).
    """
    if "pod" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'pod' axis: {mesh.axis_names}")
    i = mesh.axis_names.index("pod")
    names = tuple(a for a in mesh.axis_names if a != "pod")
    return [Mesh(np.take(mesh.devices, k, axis=i), names)
            for k in range(mesh.devices.shape[i])]
