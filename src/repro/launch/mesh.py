"""Mesh construction (DESIGN.md §6).

FUNCTIONS, not module-level constants — importing this module never touches
jax device state. Axes are (data, model), or (pod, …) with a pod tier;
`pod` composes with `data` for gradient reduction / replica serving, and
with `model` for the hierarchical (pod, model) halo exchange of full-graph
GNN cells (docs/communication.md).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh",
    "make_local_mesh",
    "make_halo_mesh",
    "data_axes",
    "halo_axes",
]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, under which
    ``with_sharding_constraint`` (``ShardingPolicy.constrain``) and shard_map
    bodies closing over mesh-sharded values both raise. Every mesh of this
    repo — drivers, examples, test scripts — is built here. ``devices``
    defaults to ``jax.devices()``; pass a described topology's devices to
    compile for a chip that is not attached."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )


def make_local_mesh():
    """1-device (data, model) mesh (CPU tests/examples)."""
    return make_mesh((1, 1), ("data", "model"))


def make_halo_mesh(pods: int, devices_per_pod: int, *, pod_map=None):
    """2-D (pod, model) mesh for hierarchical halo exchange — e.g. the
    8-device 2×4 acceptance mesh. Devices are raveled pod-major, matching
    the device→(pod, member) grouping ``build_halo_plan`` assumes.

    pod_map — optional autotuned part→pod assignment (the
    ``repro.core.autotune`` quotient mapper). Validated here for balance,
    but REALIZED by the plan, not the mesh: ``build_halo_plan(...,
    pod_map=...)`` relabels parts into pod-major device slots, so the mesh's
    device raveling never changes and any plan (default- or autotuned-map)
    runs on the same mesh object. Pass the same map to both so validation
    happens at mesh-construction time, before any compile."""
    if pod_map is not None:
        from repro.dist.halo import validate_pod_map

        validate_pod_map(pod_map, pods * devices_per_pod, pods)
    return make_mesh((pods, devices_per_pod), ("pod", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-carrying axes: ('pod','data') on the multi-pod mesh; only
    axes the mesh actually has (a (pod, model) halo mesh yields ('pod',))."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names) or ("data",)


def halo_axes(mesh) -> tuple[str, ...]:
    """The axes a full-graph halo exchange runs over: ('pod','model') when
    the mesh has a pod tier of width > 1 (hierarchical two-phase schedule),
    else ('model',) (flat single-axis schedule — a size-1 pod axis is no
    hierarchy, so e.g. ``make_halo_mesh(1, k)`` degenerates to flat)."""
    if "pod" in mesh.axis_names and mesh.shape["pod"] > 1:
        return ("pod", "model")
    return ("model",)
