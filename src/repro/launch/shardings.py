"""GNN sharding rules over a (data, model) or (pod, …) mesh (DESIGN.md §6).

Rules return NamedShardings; the activation-side policy built here is the
name→PartitionSpec contract of DESIGN.md §7.1
(`repro.dist.policy.ShardingPolicy`). Node/edge arrays shard over `model`
(the CE partition); params are replicated (tiny); sampled cells batch
blocks over (pod, data) in `repro.launch.steps`.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.policy import ShardingPolicy

__all__ = ["gnn_policy", "replicated_specs", "named", "tree_named"]


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def replicated_specs(param_tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda leaf: P(*([None] * len(leaf.shape))), param_tree)


def gnn_policy(
    mesh, comm: str = "halo",
    halo_payload: str | None = None, halo_overlap: bool = True,
) -> ShardingPolicy:
    """GNN activation policy. ``comm`` selects the full-graph communication
    schedule (DESIGN.md §8): "halo" (default — boundary-only exchange over a
    HaloPlan, inside shard_map) or "broadcast" (the paper's Fig. 5c layer-
    output all-gather via pjit sharding propagation, kept as the escape
    hatch). On a mesh with a ``pod`` tier the halo policy carries
    ``halo_axes=("pod", "model")`` so ``neighbor_table`` runs the two-phase
    hierarchical exchange (docs/communication.md). ``halo_payload`` selects
    the wire format (bf16/int8 quantized payloads, dequantized on receive)
    and ``halo_overlap`` the interior/boundary-split schedule that hides the
    collective behind interior aggregation — both per docs/communication.md
    "Overlapped schedule". Sampled-block cells have no cross-shard edges
    and run with no policy."""
    from repro.launch.mesh import halo_axes

    if comm not in ("halo", "broadcast"):
        raise ValueError(f"unknown comm mode {comm!r} (expected 'halo' or 'broadcast')")
    if comm == "halo":
        # Inside shard_map the per-device block is unsharded; constrain calls
        # are no-ops (no registered names) and the exchange is explicit.
        ha = halo_axes(mesh)
        return ShardingPolicy(
            mesh=mesh, specs={}, comm="halo", halo_axis="model",
            halo_axes=ha if len(ha) > 1 else None,
            halo_payload=halo_payload, halo_overlap=halo_overlap,
        )
    return ShardingPolicy(
        mesh=mesh,
        specs={
            "node_hidden": P("model", None),
            "edge_hidden": P("model", None),
            "irrep_hidden": P("model", None, None),
        },
    )
