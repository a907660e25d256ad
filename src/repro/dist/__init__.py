"""repro.dist — the communication layer (DESIGN.md §7).

COIN's central claim is that minimizing inter-CE communication — exchanging
only boundary ("halo") vertices between partitions instead of broadcasting
full layer outputs (paper Fig. 5c, §IV-C) — is what buys the energy win.
This package makes that contract executable on a JAX mesh:

  policy — :class:`ShardingPolicy`, the name→PartitionSpec map every model
           threads through its forward pass (``policy.constrain(x, name)``),
           with the :data:`NO_POLICY` no-op singleton for unsharded runs.
  halo   — :class:`HaloPlan` / :func:`build_halo_plan`: host-side relocation
           of a partitioned graph into contiguous per-device blocks plus the
           padded send/edge tables, and the :func:`halo_exchange` /
           :func:`halo_aggregate` collectives (all_gather / ppermute inside
           shard_map) that ship only ``k·s_max`` halo rows per device instead
           of the ``(k−1)·n_local`` rows of the broadcast schedule. On a
           2-level ``(pod, model)`` mesh the plan turns hierarchical
           (``axes=("pod", "model")``): :func:`hier_halo_exchange` /
           :func:`hier_halo_aggregate` run a two-phase collective in which
           only deduplicated remote-needed rows (``s_rem`` per device) cross
           the expensive inter-pod tier (docs/communication.md).
  delta  — :class:`GraphDelta` / :class:`DeltaPlanner`: incremental repair
           of cached plans under edge inserts/deletes on a FIXED partition
           (docs/communication.md §7) — dirty-device segment recompute,
           keep-or-grow pads, tile-level blocked-adjacency patching, and
           versioned plan-cache re-keying — plus
           :func:`apply_delta_to_graph`, the order-preserving `GraphData`
           application the serving layer's scoped invalidation builds on.
"""
from repro.dist.delta import DeltaPlanner, GraphDelta, apply_delta_to_graph
from repro.dist.halo import (
    HaloPlan,
    build_halo_plan,
    halo_aggregate,
    halo_exchange,
    hier_halo_aggregate,
    hier_halo_exchange,
)
from repro.dist.policy import NO_POLICY, ShardingPolicy

__all__ = [
    "ShardingPolicy",
    "NO_POLICY",
    "HaloPlan",
    "build_halo_plan",
    "halo_exchange",
    "halo_aggregate",
    "hier_halo_exchange",
    "hier_halo_aggregate",
    "GraphDelta",
    "DeltaPlanner",
    "apply_delta_to_graph",
]
