"""GNN step functions and the GNN cell builder.

The main path: `gnn_loss_fn` is a GNN's loss on one device (the benchmark's
training cells and ``launch.train`` call it); `halo_apply`, `halo_loss_fn`
and `gcn_device_logits` run a full graph over a HaloPlan layout inside
shard_map (DESIGN.md §8).

`build_cell` returns an abstract GNN train step — (arch × shape × mesh) →
(step_fn, abstract inputs, shardings) — that
``jax.jit(fn, in_shardings, out_shardings).lower(*abstract_inputs)``
compiles with no real allocation (every input is a ShapeDtypeStruct, params
included). Sampled cells vmap a block per data shard; graphcast trains one
example per data shard.

Full-graph cells default to the **halo** communication schedule
(DESIGN.md §8): the step runs inside shard_map over a cached
`repro.dist.halo.HaloPlan`, exchanging only boundary rows per layer
(`k·s_max` received rows/device) instead of the broadcast all-gather
(`(k−1)·n_local`). On a mesh with a ``pod`` tier the cell shards the graph
over ("pod", "model") jointly and the exchange turns hierarchical — two
phases with per-tier padding, only deduplicated remote rows crossing the
inter-pod fabric (DESIGN.md §8.3, docs/communication.md). Pass
``comm="broadcast"`` to `build_cell` for the paper-faithful Fig. 5c
schedule. `exchange_accounting` reads a cell's wire rows off its plan, and
`collective_bytes` sums the collectives of its compiled HLO.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ArchSpec, ShapeSpec
from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.launch import shardings as sh
from repro.launch.mesh import data_axes
from repro.train.optimizer import adamw

__all__ = [
    "Cell", "build_cell", "exchange_accounting", "collective_bytes",
    "gnn_loss_fn", "halo_apply", "halo_loss_fn", "gcn_device_logits",
]

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    fn: Callable
    abstract_args: tuple
    in_shardings: Any
    out_shardings: Any
    model_flops: float          # useful training FLOPs (3 × forward)
    # Full-graph cells: which communication schedule the step uses
    # ("halo" | "broadcast"; None for sampled and graphcast cells) and, for
    # halo, the HaloPlan whose static shapes the abstract batch follows.
    comm: str | None = None
    halo_plan: Any = None
    # backend="bsr" GCN halo cells: the split blocked-adjacency statistics
    # of `repro.dist.halo.plan_split_blocked_shape` ("interior"/"boundary"
    # sub-dicts + combined top-level keys); `model_flops` then follows the
    # blocked cost model (nnz_blocks·B²·F, repro.core.dataflow).
    bsr_stats: dict | None = None
    # Halo cells: the wire payload format (None/"fp32" | "bf16" | "int8")
    # and whether the interior/boundary-split overlapped schedule is on.
    halo_payload: str | None = None
    halo_overlap: bool = False

    def lower(self, mesh):
        jitted = jax.jit(
            self.fn, in_shardings=self.in_shardings, out_shardings=self.out_shardings
        )
        with mesh:
            return jitted.lower(*self.abstract_args)


def exchange_accounting(cell, shape) -> dict | None:
    """Analytic per-device wire rows of the GNN layer exchange (DESIGN.md §8,
    docs/communication.md).

    Halo cells carry their HaloPlan, so the reported bytes-moved reflects the
    boundary rows each device actually receives — not the ``(k−1)·n_local``
    a broadcast schedule would ship; both numbers are recorded so the wire
    cut is visible. Hierarchical (pod, model) plans additionally split the
    rows per tier — intra-pod (cheap links) vs inter-pod (rows crossing the
    expensive fabric) — alongside the flat single-axis baseline on the same
    partition. ``backend="bsr"`` GCN cells also carry their blocked-kernel
    statistics (nonzero 128×128 tiles and the padded-tile fraction). Cells
    without a plan (sampled or forced-broadcast) return just the comm tag.

    The overlap/compression model (`repro.core.dataflow.ExchangeCost`,
    docs/communication.md "Overlapped schedule") is reported alongside:
    ``halo_wire_bytes_per_exchange`` is what crosses the fabric under the
    cell's payload format (× bits/32 vs the fp32 total) and
    ``halo_exposed_bytes_per_exchange`` what the critical path still waits
    on (× (1 − overlap_fraction)).
    """
    plan = getattr(cell, "halo_plan", None)
    if plan is None:
        return {"comm": cell.comm} if getattr(cell, "comm", None) else None
    from repro.core.dataflow import exchange_cost
    from repro.core.quant import payload_bits

    d = shape.d_feat or 0
    payload = getattr(cell, "halo_payload", None)
    bits = payload_bits(payload)
    overlap = bool(getattr(cell, "halo_overlap", False))
    ov_frac = plan.overlap_fraction() if overlap else 0.0
    ec = exchange_cost(plan.halo_rows_per_device, d, bits, ov_frac)
    out = {
        "comm": cell.comm,
        "halo_rows_per_device": plan.halo_rows_per_device,
        "broadcast_rows_per_device": plan.broadcast_rows_per_device,
        "wire_fraction": plan.wire_fraction(),
        "halo_bytes_per_exchange": plan.halo_rows_per_device * d * 4,
        "broadcast_bytes_per_exchange": plan.broadcast_rows_per_device * d * 4,
        "payload": payload or "fp32",
        "payload_bits": bits,
        "payload_compression": ec.compression,
        "overlap": overlap,
        "overlap_fraction": ov_frac,
        "halo_wire_bytes_per_exchange": ec.wire_bytes,
        "halo_exposed_bytes_per_exchange": ec.exposed_bytes,
        "boundary_rows_max_device": int(plan.boundary_rows_per_device().max(initial=0)),
        "interior_rows_min_device": int(plan.interior_rows_per_device().min(initial=0)),
    }
    if getattr(cell, "bsr_stats", None):
        out["bsr"] = dict(cell.bsr_stats)
    if plan.is_hierarchical:
        out.update(
            axes=list(plan.axes),
            pods=plan.n_pods,
            intra_pod_rows_per_device=plan.intra_pod_rows_per_device,
            inter_pod_rows_per_device=plan.inter_pod_rows_per_device,
            inter_pod_rows_crossing=plan.inter_pod_rows_crossing,
            flat_inter_pod_rows_crossing=plan.flat_inter_pod_rows_crossing,
            inter_pod_bytes_crossing=plan.inter_pod_rows_crossing * d * 4,
            flat_inter_pod_bytes_crossing=plan.flat_inter_pod_rows_crossing * d * 4,
        )
    return out


_DTYPE_BYTES = {
    "pred": 0.125, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute",
)
_COLL_RE = re.compile(
    r"=\s*([^=]*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\("
)


def _shape_bytes(type_str: str) -> float:
    """Bytes of one HLO shape string like 'bf16[256,4096]' or a tuple."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-shape bytes of every collective op in the post-SPMD HLO.

    The result shape of each collective instruction line —
    `%x = f32[170,75]{1,0} all-reduce(...)` — is per-device shaped after SPMD
    partitioning, so the sum is the per-device wire volume entering the
    network. `-done` ops carry the same tuple as their `-start`; only lines
    that themselves name a collective op with an argument list are counted,
    and `-done`/`-update` variants don't match the pattern.
    """
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
    out["total"] = sum(out.values())
    return out


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def _opt_specs(p_specs):
    """AdamW state {m, v, step}: m/v mirror param specs; step replicated."""
    return {"m": p_specs, "v": p_specs, "step": P()}


def _lead_shardings(mesh, axes, tree):
    """Shard every leaf of ``tree`` along its leading axis over ``axes``."""
    return jax.tree_util.tree_map(
        lambda l: sh.named(mesh, P(axes, *([None] * (len(l.shape) - 1)))), tree
    )


# ======================================================================== GNN
def _bsr_tables(batch: dict, prefix: str = "bsr_"):
    """The ``(vals, cols, lens)`` blocked-adjacency triple a coin_gcn batch
    carries under ``<prefix>vals``/``cols``/``lens`` (None without one)."""
    if prefix + "vals" not in batch:
        return None
    return tuple(batch[prefix + k] for k in ("vals", "cols", "lens"))


def gnn_loss_fn(arch_id: str, cfg, policy: ShardingPolicy, n_loss_nodes: int | None = None):
    """``loss(params, batch)`` of a GNN arch on one device: cross-entropy
    for coin_gcn (``backend="bsr"`` reads its blocked adjacency from the
    batch's ``bsr_vals``/``bsr_cols``/``bsr_lens``), GraphCast's weighted
    MSE on one example for graphcast (its batch: `repro.models.graphcast`;
    ``policy`` unused, the model is not sharded), regression elsewhere
    (sliced to the first ``n_loss_nodes`` rows for sampled blocks — losses
    are computed on the seed nodes only)."""

    def _mse(pred, target):
        if n_loss_nodes is not None:
            pred = pred[:n_loss_nodes]
        return jnp.mean(jnp.square(pred - target))

    if arch_id == "egnn":
        from repro.models.egnn import egnn_forward

        def loss(params, batch):
            pred, _ = egnn_forward(
                params, batch["feats"], batch["pos"], batch["senders"],
                batch["receivers"], cfg, policy,
            )
            return _mse(pred, batch["target"])
    elif arch_id == "graphcast":
        from repro.models.graphcast import graphcast_loss

        def loss(params, batch):
            return graphcast_loss(params, batch, cfg)
    elif arch_id == "equiformer-v2":
        from repro.models.equiformer_v2 import equiformer_forward

        def loss(params, batch):
            pred = equiformer_forward(
                params, batch["feats"], batch["pos"], batch["senders"],
                batch["receivers"], cfg, policy,
            )
            return _mse(pred, batch["target"])
    elif arch_id == "pna":
        from repro.models.pna import pna_forward

        def loss(params, batch):
            pred = pna_forward(
                params, batch["feats"], batch["senders"], batch["receivers"], cfg, policy
            )
            return _mse(pred, batch["target"])
    elif arch_id == "coin_gcn":
        from repro.models.gcn import gcn_loss

        def loss(params, batch):
            return gcn_loss(
                params, batch["feats"], batch["senders"], batch["receivers"],
                batch["edge_weight"], batch["labels"], batch["label_mask"], cfg, policy,
                adjacency=_bsr_tables(batch),
            )
    else:
        raise KeyError(arch_id)
    return loss


def _gnn_params(arch_id: str, cfg):
    """Abstract float32 params of a GNN arch."""
    key = jax.random.PRNGKey(0)
    if arch_id == "egnn":
        from repro.models.egnn import egnn_init

        return jax.eval_shape(lambda k: egnn_init(k, cfg), key)
    if arch_id == "graphcast":
        from repro.models.graphcast import graphcast_init

        return jax.eval_shape(lambda k: graphcast_init(k, cfg), key)
    if arch_id == "equiformer-v2":
        from repro.models.equiformer_v2 import equiformer_init

        return jax.eval_shape(lambda k: equiformer_init(k, cfg), key)
    if arch_id == "pna":
        from repro.models.pna import pna_init

        return jax.eval_shape(lambda k: pna_init(k, cfg), key)
    if arch_id == "coin_gcn":
        from repro.models.gcn import gcn_init

        return jax.eval_shape(lambda k: gcn_init(k, cfg), key)
    raise KeyError(arch_id)


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _gnn_sizes(shape: ShapeSpec, pad_mult: int) -> tuple[int, int]:
    """(nodes, edges) of the device graph: packed for molecule batches,
    fanout-expanded for sampled blocks, padded to the shard divisor."""
    if shape.batch_nodes is not None:       # sampled block
        n, e, frontier = shape.batch_nodes, 0, shape.batch_nodes
        for f in shape.fanout:
            e += frontier * f
            frontier *= f
            n += frontier
    elif shape.n_graphs is not None:        # packed small-graph batch
        n, e = shape.n_nodes * shape.n_graphs, shape.n_edges * shape.n_graphs
    else:                                   # one full graph
        n, e = shape.n_nodes, shape.n_edges
    return _pad_to(n, pad_mult), _pad_to(e, pad_mult)


def _graphcast_batch_abstract(cfg, lead: tuple = ()) -> dict:
    """Abstract GraphCast batch (`repro.models.graphcast`), sized by the
    geometry `repro.graph.sphere` builds for ``cfg``."""
    from repro.graph.sphere import graph_shapes

    batch = {k: _sds(lead + shape, jnp.dtype(dtype))
             for k, shape, dtype in graph_shapes(*cfg.geometry)}
    n_grid = batch["grid_nodes"].shape[-2]
    batch["grid_inputs"] = _sds(lead + (n_grid, cfg.d_grid_in), F32)
    batch["grid_target"] = _sds(lead + (n_grid, cfg.n_vars), F32)
    return batch


def _gnn_batch_abstract(arch_id: str, shape: ShapeSpec, cfg, n_blocks: int | None, pad_mult: int):
    """Abstract batch dict. n_blocks=None → single global graph; else a
    leading block axis (one sampled block per data shard)."""
    n, e = _gnn_sizes(shape, pad_mult if n_blocks is None else 1)
    lead = () if n_blocks is None else (n_blocks,)
    batch = {
        "feats": _sds(lead + (n, shape.d_feat), F32),
        "senders": _sds(lead + (e,), I32),
        "receivers": _sds(lead + (e,), I32),
    }
    if arch_id in ("egnn", "equiformer-v2"):
        batch["pos"] = _sds(lead + (n, 3), F32)
    if arch_id == "coin_gcn":
        batch["edge_weight"] = _sds(lead + (e,), F32)
        batch["labels"] = _sds(lead + (n,), I32)
        batch["label_mask"] = _sds(lead + (n,), F32)
    else:
        n_out = getattr(cfg, "d_out", 1)
        n_tgt = shape.batch_nodes if n_blocks is not None else n
        batch["target"] = _sds(lead + (n_tgt, n_out), F32)
    return batch


def _gnn_flops(arch_id: str, shape: ShapeSpec, cfg, bsr_stats: dict | None = None) -> float:
    """Useful forward FLOPs (2 × MACs of the defining matmuls per arch).

    ``bsr_stats`` (a `repro.dist.halo.plan_blocked_shape` record) switches
    the coin_gcn aggregation term to the blocked cost model, the kernel's
    real nnz_blocks·B²·F work.
    """
    if arch_id == "graphcast":
        from repro.models.graphcast import forward_flops

        return forward_flops(cfg)
    n, e = float(shape.n_nodes), float(shape.n_edges)
    L = cfg.n_layers
    if arch_id == "equiformer-v2":
        C, lmax, mmax = cfg.d_hidden, cfg.l_max, cfg.m_max
        K = (lmax + 1) ** 2
        so2 = ((lmax + 1) * C) ** 2 + 2 * sum(
            2 * ((lmax + 1 - m) * C) ** 2 for m in range(1, mmax + 1)
        )
        rot = 2 * sum((2 * l + 1) ** 2 for l in range(lmax + 1)) * C   # D + Dᵀ apply
        attn = (2 * C + cfg.n_rbf) * C + C * cfg.n_heads
        ffn_n = C * 2 * C + 2 * C * C + lmax * C * C                   # scalar MLP + per-l mix
        return 2.0 * L * (e * (so2 + rot + attn) + n * ffn_n)
    if arch_id == "egnn":
        d = cfg.d_hidden
        per_e = (2 * d + 1) * d + d * d + (d * d + d)                  # φ_e (2-layer) + φ_x
        per_n = 2 * d * d + d * d                                      # φ_h
        return 2.0 * L * (e * per_e + n * per_n)
    if arch_id == "pna":
        d = cfg.d_hidden
        per_e = 2 * d * d                                              # pre-MLP on (h_i‖h_j)
        per_n = (1 + cfg.n_agg_feats) * d * d                          # post-MLP on 13·d concat
        return 2.0 * L * (e * per_e + n * per_n)
    if arch_id == "coin_gcn":
        bsr = bsr_stats
        total = 0.0
        for d_in, d_out in zip(cfg.layer_dims[:-1], cfg.layer_dims[1:]):
            if bsr is not None:
                # Blocked cost: the ragged MXU kernel runs nnz_blocks·B²
                # MACs per output feature, not E (repro.core.dataflow).
                from repro.core.dataflow import blocked_multiply_count

                total += blocked_multiply_count(
                    n, bsr["nnz_blocks"], d_in, d_out, bsr["block"]
                ).feature_first
            else:
                total += n * d_in * d_out + e * d_out                  # feature-first
        return 2.0 * total
    raise KeyError(arch_id)


def _shape_halo_plan(n: int, e: int, k: int, pods: int = 1):
    """Cached HaloPlan for the (n, e) shape-statistics synthetic graph.

    Abstract cells have no real graph — they run on the deterministic
    exact-count synthetic (DESIGN.md §5), partitioned with the
    locality-seeking BFS+refine that keeps export sets small
    (DESIGN.md §7.3). The plan is memoized per (graph, k, axes) in
    `repro.dist.halo` (``pods > 1`` caches under the ("pod", "model") axes
    tuple, side by side with the flat plan), so every layer/epoch/cell over
    the same shape reuses one host-side relocation; the deterministic string
    key means a cache hit skips graph synthesis and partitioning entirely.
    """
    from repro.dist.halo import build_halo_plan, cached_halo_plan

    axes = ("pod", "model") if pods > 1 else ("model",)

    def build():
        from repro.core.partition import partition_graph
        from repro.graph.generators import citation_like

        g = citation_like(n, e, seed=0)
        part = partition_graph(n, g.edge_index, k, method="bfs", seed=0, refine=True)
        return build_halo_plan(part, g.edge_index, axes=axes, pods=pods)

    return cached_halo_plan(
        f"citation_like:n{n}:e{e}:seed0", k,
        axes if pods > 1 else "model", pods=pods, builder=build,
    )


def gcn_device_logits(cfg):
    """``logits(params, b, pol)`` of coin_gcn on one device's HaloPlan block
    (a `halo_apply` device function). ``backend="bsr"`` reads the split pair
    of `repro.dist.halo.plan_split_blocked_adjacency` from ``b``: interior
    ``bsr_`` and boundary ``bsr_b`` tables — the overlapped schedule, where
    interior tiles aggregate the local block while the boundary tables
    consume the halo exchange."""
    from repro.models.gcn import gcn_forward

    def logits(params, b, pol):
        return gcn_forward(
            params, b["feats"], b["senders"], b["receivers"], b["edge_w"], cfg, pol,
            adjacency=_bsr_tables(b), adjacency_boundary=_bsr_tables(b, "bsr_b"),
        ).astype(F32)

    return logits


def _gnn_halo_device_loss(arch_id: str, cfg):
    """Per-device (weighted_sum, weight) of the arch's loss over one block.

    Runs inside the shard_map body: every array is this device's slice of the
    HaloPlan layout, ``pol`` has the device's export rows bound, and padding
    (edge_w == 0 edges, rows ≥ part_size) is masked out so the psum-combined
    loss equals the global single-device loss exactly.
    """

    def device_loss(params, b, pol):
        edge_mask = (b["edge_w"] > 0).astype(F32)
        if arch_id == "coin_gcn":
            logits = gcn_device_logits(cfg)(params, b, pol)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, b["labels"][:, None], axis=-1)[:, 0]
            return ((lse - gold) * b["label_mask"]).sum(), b["label_mask"].sum()
        if arch_id == "pna":
            from repro.models.pna import pna_forward

            pred = pna_forward(
                params, b["feats"], b["senders"], b["receivers"], cfg, pol, edge_mask=edge_mask
            )
        elif arch_id == "egnn":
            from repro.models.egnn import egnn_forward

            pred, _ = egnn_forward(
                params, b["feats"], b["pos"], b["senders"], b["receivers"], cfg, pol,
                edge_mask=edge_mask,
            )
        elif arch_id == "equiformer-v2":
            from repro.models.equiformer_v2 import equiformer_forward

            pred = equiformer_forward(
                params, b["feats"], b["pos"], b["senders"], b["receivers"], cfg, pol,
                edge_mask=edge_mask,
            )
        else:
            raise KeyError(arch_id)
        sq = jnp.sum(jnp.square(pred.astype(F32) - b["target"]), axis=-1)
        return (sq * b["node_mask"]).sum(), b["node_mask"].sum() * pred.shape[-1]

    return device_loss


def _halo_spec_axes(mesh):
    from repro.launch.mesh import halo_axes

    axes = halo_axes(mesh)
    return axes if len(axes) > 1 else "model"


def halo_apply(device_fn, mesh, policy: ShardingPolicy):
    """``f(params, batch)`` running ``device_fn(params, b, pol)`` on every
    device of a HaloPlan layout, inside shard_map over the mesh's halo axes
    (("model",) flat, ("pod", "model") hierarchical).

    Every batch leaf carries one leading slice per device (``(k, n_local,
    …)`` node arrays, ``(k, e_local)`` edge arrays, the plan's export rows
    and, for coin_gcn ``backend="bsr"``, the per-shard blocked tables);
    ``b`` is this device's slice and ``pol`` the policy with its export rows
    bound. Outputs stack along a leading device axis."""
    spec_axes = _halo_spec_axes(mesh)
    hier = isinstance(spec_axes, tuple)

    def f(params, batch):
        keys = sorted(batch)

        def body(*args):
            b = {kk: a[0] for kk, a in zip(keys, args)}
            if hier:
                pol = policy.bind_halo(send_loc=b["send_loc"], send_rem=b["send_rem"])
            else:
                pol = policy.bind_halo(b["send_idx"])
            return jax.tree_util.tree_map(lambda o: o[None], device_fn(params, b, pol))

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(spec_axes),) * len(keys), out_specs=P(spec_axes),
            # pallas_call (the backend="bsr" blocked aggregation) has no
            # replication rule; psum-combined scalars make rep moot anyway.
            check_vma=False,
        )(*[batch[kk] for kk in keys])

    return f


def halo_loss_fn(arch_id: str, cfg, mesh, policy: ShardingPolicy):
    """``loss(params, batch)`` of a full-graph GNN over a HaloPlan layout
    (`halo_apply`): per-device sums psum-combined, so it equals the
    one-device loss on the whole graph."""
    spec_axes = _halo_spec_axes(mesh)
    device_loss = _gnn_halo_device_loss(arch_id, cfg)

    def per_device(params, b, pol):
        wsum, wcnt = device_loss(params, b, pol)
        return jax.lax.psum(wsum, spec_axes) / jnp.maximum(
            jax.lax.psum(wcnt, spec_axes), 1.0
        )

    f = halo_apply(per_device, mesh, policy)
    return lambda params, batch: f(params, batch).mean()


def _gnn_halo_batch_abstract(
    arch_id: str, shape: ShapeSpec, cfg, plan, bsr_stats: dict | None = None
) -> dict:
    """Abstract batch in the HaloPlan blocked layout: per-node arrays are
    (k, n_local, …), per-edge arrays (k, e_local, …), plus the plan tables
    (flat: send_idx; hierarchical: the send_loc/send_rem tier pair).
    ``backend="bsr"`` GCN cells additionally carry the per-shard blocked
    adjacency tables, sized by `repro.dist.halo.plan_split_blocked_shape`
    (an interior triple over local columns plus a boundary triple over the
    halo-only columns — the overlapped schedule's pair) so no tile is ever
    materialized for abstract cells."""
    k, n_local = plan.k, plan.n_local
    if plan.is_hierarchical:
        sloc, srem, sl, rl, ew = plan.abstract_inputs()
        send = {"send_loc": sloc, "send_rem": srem}
    else:
        si, sl, rl, ew = plan.abstract_inputs()
        send = {"send_idx": si}
    batch = {
        "feats": _sds((k, n_local, shape.d_feat), F32),
        **send,
        "senders": sl,
        "receivers": rl,
        "edge_w": ew,
    }
    if arch_id in ("egnn", "equiformer-v2"):
        batch["pos"] = _sds((k, n_local, 3), F32)
    if arch_id == "coin_gcn":
        if bsr_stats is not None:
            for tag, prefix in (("interior", "bsr_"), ("boundary", "bsr_b")):
                st = bsr_stats[tag]
                R, T, B = st["n_block_rows"], st["max_nnzb"], st["block"]
                batch[prefix + "vals"] = _sds((k, R, T, B, B), F32)
                batch[prefix + "cols"] = _sds((k, R, T), I32)
                batch[prefix + "lens"] = _sds((k, R), I32)
        batch["labels"] = _sds((k, n_local), I32)
        batch["label_mask"] = _sds((k, n_local), F32)
    else:
        batch["target"] = _sds((k, n_local, getattr(cfg, "d_out", 1)), F32)
        batch["node_mask"] = _sds((k, n_local), F32)
    return batch


def _train_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, cfg, total_loss, batch_abs, batch_spec,
    **fields,
) -> Cell:
    """The AdamW train step of ``total_loss`` every GNN cell compiles:
    params replicated, ``fields`` the rest of the `Cell`."""
    params_abs = _gnn_params(spec.arch_id, cfg)
    p_specs = sh.replicated_specs(params_abs)
    p_shard = sh.tree_named(mesh, p_specs)

    opt = adamw(lr=1e-3)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = sh.tree_named(mesh, _opt_specs(p_specs))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(total_loss)(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    return Cell(
        spec.arch_id, shape.name,
        train_step,
        (params_abs, opt_abs, batch_abs),
        (p_shard, o_shard, batch_spec),
        (p_shard, o_shard, sh.named(mesh, P())),
        **fields,
    )


def _gnn_halo_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, cfg, payload: str | None = None,
) -> Cell:
    """Full-graph GNN train cell over the halo schedule (the default path).

    The whole step runs inside shard_map: each device holds one HaloPlan
    block and every layer's neighbor aggregation goes through boundary
    collectives via ``policy.neighbor_table`` (DESIGN.md §8). On a flat mesh
    the exchange runs over the "model" axis (``k·s_max`` received rows vs
    the broadcast schedule's ``(k−1)·n_local``); on a mesh with a ``pod``
    tier the graph shards over (pod, model) jointly and the exchange is the
    two-phase hierarchical collective — only deduplicated remote rows cross
    the inter-pod fabric (docs/communication.md).

    ``payload`` quantizes the wire (bf16/int8, dequantized on receive) and
    the coin_gcn cell runs the overlapped schedule: segment backend via the
    interior/boundary split aggregation, bsr backend via the split blocked
    tables of `plan_split_blocked_adjacency` — either way layer ℓ's
    boundary collective is consumed only by the boundary term, so XLA's
    latency-hiding scheduler overlaps it with interior compute
    (docs/communication.md "Overlapped schedule").
    """
    from repro.launch.mesh import halo_axes

    axes = halo_axes(mesh)
    hier = len(axes) > 1
    pods = mesh.shape["pod"] if hier else 1
    k = pods * mesh.shape["model"]
    spec_axes = axes if hier else "model"
    n_raw, e_raw = _gnn_sizes(shape, pad_mult=1)
    plan = _shape_halo_plan(n_raw, e_raw, k, pods)
    policy = sh.gnn_policy(mesh, comm="halo", halo_payload=payload)
    bsr_stats = None
    if spec.arch_id == "coin_gcn" and getattr(cfg, "backend", "segment") == "bsr":
        from repro.dist.halo import plan_split_blocked_shape

        split = plan_split_blocked_shape(plan)
        st_i, st_b = split["interior"], split["boundary"]
        nnzb = st_i["nnz_blocks"] + st_b["nnz_blocks"]
        grid = k * (
            st_i["n_block_rows"] * st_i["max_nnzb"]
            + st_b["n_block_rows"] * st_b["max_nnzb"]
        )
        bsr_stats = {
            "block": st_i["block"],
            "nnz_blocks": nnzb,
            "padded_tile_fraction": 1.0 - nnzb / max(grid, 1),
            "overlap_fraction": split["overlap_fraction"],
            "interior": st_i,
            "boundary": st_b,
        }

    batch_abs = _gnn_halo_batch_abstract(spec.arch_id, shape, cfg, plan, bsr_stats)
    return _train_cell(
        spec, shape, mesh, cfg, halo_loss_fn(spec.arch_id, cfg, mesh, policy),
        batch_abs, _lead_shardings(mesh, spec_axes, batch_abs),
        model_flops=_gnn_flops(spec.arch_id, shape, cfg, bsr_stats) * 3.0,
        comm="halo",
        halo_plan=plan,
        bsr_stats=bsr_stats,
        halo_payload=payload,
        halo_overlap=policy.halo_overlap,
    )


def _graphcast_cell(spec: ArchSpec, shape: ShapeSpec, mesh, cfg) -> Cell:
    """GraphCast trains data-parallel: one example per data shard (its own
    copy of the graphs), weights replicated, the loss the shards' mean."""
    da = data_axes(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in da]))
    batch_abs = _graphcast_batch_abstract(cfg, (n_data,))
    loss_fn = gnn_loss_fn(spec.arch_id, cfg, NO_POLICY)

    def total_loss(params, batch):
        return jnp.mean(jax.vmap(lambda b: loss_fn(params, b))(batch))

    return _train_cell(
        spec, shape, mesh, cfg, total_loss, batch_abs, _lead_shardings(mesh, da, batch_abs),
        model_flops=_gnn_flops(spec.arch_id, shape, cfg) * 3.0 * n_data,
    )


def _gnn_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh,
    comm: str | None = None, payload: str | None = None,
) -> Cell:
    cfg = spec.make_config(shape)
    if spec.arch_id == "graphcast":
        return _graphcast_cell(spec, shape, mesh, cfg)
    da = data_axes(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in da]))
    msize = mesh.shape["model"]
    sampled = shape.batch_nodes is not None
    if comm is None:
        comm = "broadcast" if sampled else "halo"
    if not sampled and comm == "halo":
        return _gnn_halo_cell(spec, shape, mesh, cfg, payload=payload)
    n_blocks = n_data if sampled else None
    policy = NO_POLICY if sampled else sh.gnn_policy(mesh, comm="broadcast")

    loss_fn = gnn_loss_fn(
        spec.arch_id, cfg, policy, n_loss_nodes=shape.batch_nodes if sampled else None
    )
    batch_abs = _gnn_batch_abstract(spec.arch_id, shape, cfg, n_blocks, pad_mult=msize)

    if sampled:
        batch_spec = _lead_shardings(mesh, da, batch_abs)

        def total_loss(params, batch):
            losses = jax.vmap(lambda b: loss_fn(params, b))(batch)
            return jnp.mean(losses)
    else:
        # Shard the big axis (nodes or edges) over `model`.
        batch_spec = _lead_shardings(mesh, "model", batch_abs)
        total_loss = loss_fn

    # train = fwd + bwd ≈ 3× forward FLOPs; sampled cells run one block per
    # data shard, so FLOPs count block sizes, not the full graph.
    if sampled:
        blk = dataclasses.replace(
            shape,
            n_nodes=int(batch_abs["feats"].shape[1]) * n_blocks,
            n_edges=int(batch_abs["senders"].shape[1]) * n_blocks,
        )
        flops = _gnn_flops(spec.arch_id, blk, cfg) * 3.0
    else:
        flops = _gnn_flops(spec.arch_id, shape, cfg) * 3.0
    return _train_cell(
        spec, shape, mesh, cfg, total_loss, batch_abs, batch_spec,
        model_flops=flops, comm=None if sampled else "broadcast",
    )


# ==================================================================== factory
def build_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh,
    comm: str | None = None, payload: str | None = None,
) -> Cell:
    """The abstract train step of a GNN arch on ``mesh``. The aggregation
    backend is the configuration's own (``spec.make_config(shape)``).

    comm selects the full-graph communication schedule: None → "halo" for
    full-graph cells (DESIGN.md §8); "broadcast" → the paper-faithful
    layer-output all-gather. payload selects the halo wire format
    (None/"fp32" | "bf16" | "int8" — quantized boundary rows, dequantized on
    receive; docs/communication.md "Overlapped schedule"); halo cells only."""
    if spec.family == "gnn":
        return _gnn_cell(spec, shape, mesh, comm=comm, payload=payload)
    raise KeyError(spec.family)
