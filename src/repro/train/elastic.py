"""Elastic scaling: re-mesh and re-shard after node loss (DESIGN.md §3).

The contract at pod scale: a failed host removes a slice of devices; the
controller (a) picks the largest still-healthy mesh from the preference
ladder, (b) restores the latest checkpoint with shardings rebuilt for the
new mesh (checkpoints are mesh-agnostic host arrays — train/checkpoint.py),
(c) rescales the data pipeline to the new data-parallel width. Everything
here is pure logic over device lists, so it is fully unit-testable on CPU.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np

__all__ = [
    "MeshPlan",
    "elastic_replan",
    "relocate_state_tree",
    "reshard_tree",
    "scale_batch",
]


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.shape))

    def build(self, devices: Sequence[Any] | None = None):
        if devices is None:
            from repro.launch.mesh import make_mesh

            return make_mesh(self.shape, self.axes)
        arr = np.asarray(devices[: self.n_devices]).reshape(self.shape)
        return jax.sharding.Mesh(arr, self.axes)


def elastic_replan(
    n_healthy: int,
    model_shards: int,
    axes: tuple[str, ...] = ("data", "model"),
    *,
    graph_key: str | None = None,
) -> MeshPlan:
    """Largest mesh ≤ n_healthy that preserves the model-parallel degree.

    Model-parallel shards hold partitioned state (the COIN CE partition —
    can't shrink without re-partitioning), so the data axis absorbs the
    loss: data' = floor(n_healthy / model_shards). A **pure resize** (the
    model degree survives, only the data axis narrows) keeps the node→CE
    partition intact, so NO cached halo plan is touched — plan-cache
    ``evictions`` stays 0 and the delta path (`repro.dist.delta`) keeps
    repairing the same plan objects across the resize.

    Only when fewer than one data replica remains do we halve the model
    shards — a re-partition event: the k of the node→CE partition changed,
    so the boundary relocation is stale and the affected plans are evicted
    (DESIGN.md §8). Pass ``graph_key`` (the training graph's fingerprint or
    the planner's current versioned key) to scope that eviction to the one
    graph being re-partitioned — every ``(axes, n_pods)`` flavor of it goes
    in the one call — instead of flushing every graph's plans.
    """
    if n_healthy < 1:
        raise ValueError("no healthy devices")
    m = model_shards
    while m > 1 and n_healthy < m:
        m //= 2
    if m != model_shards:
        from repro.dist.halo import invalidate_halo_plans

        invalidate_halo_plans(graph_key)
    d = max(n_healthy // m, 1)
    return MeshPlan(shape=(d, m), axes=axes)


def reshard_tree(tree: Any, mesh, spec_tree: Any) -> Any:
    """device_put every leaf with NamedShardings over the (new) mesh."""
    def put(leaf, spec):
        return jax.device_put(leaf, jax.sharding.NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(
        put, tree, spec_tree, is_leaf=lambda x: x is None or hasattr(x, "shape")
    )


def relocate_state_tree(old_layout: Any, new_plan: Any, tree: Any) -> Any:
    """Carry live per-node state across an in-place re-localization.

    ``old_layout`` is a `repro.dist.halo.PlanLayout` snapshot taken BEFORE
    `repro.dist.delta.DeltaPlanner.relocalize` (the relocalize report's
    ``old_layout``); ``new_plan`` is any plan/layout in the NEW row order.
    Every leaf whose leading dims match the old blocked shape
    ``(k, n_local)`` — relocated features, per-node optimizer moments, layer
    activations — is routed ``restore_node_array(old)`` →
    ``relocate_node_array(new)``: back to global node order, then into the
    fresh blocks. The round trip is EXACT (pure gathers, no arithmetic), so
    a forward pass after relocation is bit-equivalent modulo row order.
    Leaves of any other shape (dense weights, scalars, None) pass through
    untouched.
    """
    from repro.dist.halo import relocate_node_array, restore_node_array

    old_shape = (int(old_layout.k), int(old_layout.n_local))

    def move(leaf):
        if leaf is None or not hasattr(leaf, "shape"):
            return leaf
        if tuple(np.asarray(leaf).shape[:2]) != old_shape:
            return leaf
        return relocate_node_array(
            new_plan, restore_node_array(old_layout, np.asarray(leaf)))

    return jax.tree_util.tree_map(
        move, tree, is_leaf=lambda x: x is None or hasattr(x, "shape"))


def scale_batch(global_batch: int, old_data_shards: int, new_data_shards: int) -> int:
    """Keep per-device batch constant across a re-shard (linear-scaling rule:
    the caller rescales LR by new/old)."""
    per_device = max(global_batch // old_data_shards, 1)
    return per_device * new_data_shards
