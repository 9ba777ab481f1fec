"""Production mesh builders.

Target: TPU v5e pods. Single pod = 256 chips as a (data=16, model=16) mesh;
multi-pod = 2 pods = 512 chips as (pod=2, data=16, model=16). Functions, not
module constants — importing this module never touches jax device state.

All builders are process-aware: ``jax.make_mesh`` lays the mesh out over
the *global* device list, so after ``launch.multiprocess`` bring-up the
same ``make_mule_mesh(pod, data)`` call in every process yields one
multi-host mesh (device order groups by process, so a ``P(data_axis)``
row sharding block-partitions the mule axis by process).
"""
from __future__ import annotations

import math

import jax


def _check_device_count(shape, axes) -> None:
    """Fail fast with both numbers when the shape outruns the device pool.

    Without this a mismatch surfaces deep inside ``Mesh`` construction as
    a reshape error that names neither the requested shape nor the pool.
    """
    need = math.prod(shape)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh shape {dict(zip(axes, shape))} needs {need} devices but "
            f"jax.device_count()={have} "
            f"({jax.process_count()} process(es) x "
            f"{jax.local_device_count()} local device(s))")


def make_production_mesh(*, multi_pod: bool = False, model_parallel: int = 16):
    """Production mesh. ``model_parallel`` re-balances the LOGICAL data/model
    split over the same 256 chips/pod (a per-architecture tuning knob: TP
    degree must divide the attention head count or GSPMD falls back to
    score all-reduces — see EXPERIMENTS.md §Perf pair 2)."""
    data = 256 // model_parallel
    shape = (2, data, model_parallel) if multi_pod else (data, model_parallel)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _check_device_count(shape, axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over host devices for CI-scale distributed tests."""
    if pod:
        _check_device_count((pod, data, model), ("pod", "data", "model"))
        return jax.make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 3)
    _check_device_count((data, model), ("data", "model"))
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_mule_mesh(pod: int, data: int, *, pod_axis: str = "pod",
                   data_axis: str = "data"):
    """(pod, data) mesh for the mule-sharded scenario engine.

    The shape the roofline-driven ``suggest_mesh_shape`` emits and
    ``run_population_distributed(mesh=None)`` consumes; ``pod_axis=""``
    builds the single-axis data-only mesh a podless ``DistributedConfig``
    expects. Under multi-process bring-up the mesh spans every process's
    devices — pass the *global* shard counts.
    """
    if not pod_axis:
        if pod != 1:
            raise ValueError(f"pod={pod} needs a pod axis name")
        _check_device_count((data,), (data_axis,))
        return jax.make_mesh((data,), (data_axis,))
    _check_device_count((pod, data), (pod_axis, data_axis))
    return jax.make_mesh((pod, data), (pod_axis, data_axis))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the global batch / population dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
