"""Cell builder: (arch × shape × mesh) → (step_fn, abstract inputs, shardings).

`build_cell` returns everything `dryrun.py` needs to
``jax.jit(fn, in_shardings, out_shardings).lower(*abstract_inputs)`` with no
real allocation (every input is a ShapeDtypeStruct, params included — the
same pattern the assignment's shannon/kernels reference uses).

Step kinds per family:
  lm/train      — loss + grads + AdamW update        (train_step)
  lm/prefill    — last-position logits               (serve_step)
  lm/decode     — one token against the KV cache     (serve_step)
  gnn/graph     — regression loss + grads + AdamW    (train_step; sampled
                  cells vmap a block per data shard, graphcast one
                  example per data shard)
  recsys/train  — BCE loss + grads + AdamW
  recsys/serve  — batched logits
  recsys/retrieval — 1×N candidate scoring

Full-graph GNN cells default to the **halo** communication schedule
(DESIGN.md §8): the step runs inside shard_map over a cached
`repro.dist.halo.HaloPlan`, exchanging only boundary rows per layer
(`k·s_max` received rows/device) instead of the broadcast all-gather
(`(k−1)·n_local`). On a mesh with a ``pod`` tier the cell shards the graph
over ("pod", "model") jointly and the exchange turns hierarchical — two
phases with per-tier padding, only deduplicated remote rows crossing the
inter-pod fabric (DESIGN.md §8.3, docs/communication.md). Pass
``comm="broadcast"`` to `build_cell` for the paper-faithful Fig. 5c
schedule (the escape hatch and the dry-run baseline).
"""
from __future__ import annotations

import dataclasses
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

__all__ = ["Cell", "build_cell", "gnn_loss_fn", "halo_apply", "halo_loss_fn", "gcn_device_logits"]

F32 = jnp.float32
BF16 = jnp.bfloat16
I32 = jnp.int32


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    fn: Callable
    abstract_args: tuple
    in_shardings: Any
    out_shardings: Any
    model_flops: float          # 6·N·D-style useful-FLOPs estimate
    note: str = ""
    # Cost correction: XLA cost_analysis counts a rolled lax.scan body ONCE,
    # so deep layer stacks under-report FLOPs/bytes/collectives. When set,
    # each entry is (small UNROLLED variant, its group count); the dry-run
    # fits cost(g) = fixed + g·delta with delta clamped ≥ 0 (XLA's SPMD
    # choices differ slightly between programs, so a raw two-point
    # extrapolation can go negative) and evaluates at `cost_groups`. A single
    # entry means "use its cost verbatim". memory_analysis / compile proof
    # always come from this Cell's real rolled program.
    cost_cells: list[tuple["Cell", float]] | None = None
    cost_groups: float = 1.0
    donate_argnums: tuple = ()
    # GNN full-graph cells: which communication schedule the step uses
    # ("halo" | "broadcast"; None for non-GNN / sampled cells) and, for halo,
    # the HaloPlan whose static shapes the abstract batch follows — the
    # dry-run reads wire accounting (k·s_max vs (k−1)·n_local) off it.
    comm: str | None = None
    halo_plan: Any = None
    # backend="bsr" GCN cells: the blocked-adjacency statistics of
    # `repro.dist.halo.plan_blocked_shape` (nonzero 128×128 tiles,
    # padded-tile fraction) — the dry-run reports them in the `exchange`
    # record and `model_flops` is computed from the blocked cost model
    # (nnz_blocks·B²·F, repro.core.dataflow) instead of the edge count.
    # Halo cells carry the split record of `plan_split_blocked_shape`
    # ("interior"/"boundary" sub-dicts + combined top-level keys).
    bsr_stats: dict | None = None
    # Halo cells: the wire payload format (None/"fp32" | "bf16" | "int8")
    # and whether the interior/boundary-split overlapped schedule is on —
    # the dry-run's exchange accounting reads both (ExchangeCost).
    halo_payload: str | None = None
    halo_overlap: bool = False

    def lower(self, mesh):
        jitted = jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with mesh:
            return jitted.lower(*self.abstract_args)


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def _abstract_tree(tree):
    return jax.tree_util.tree_map(lambda l: _sds(l.shape, l.dtype), tree)


# ========================================================================= LM
def _lm_cost_cells(
    spec: ArchSpec, shape: ShapeSpec, mesh, cfg
) -> tuple[list[tuple[Cell, float]], float]:
    """Two small fully-unrolled variants for cost extrapolation.

    period = the layer-pattern repeat (gemma3's 5:1 group, else 1 layer);
    cost(L) ≈ fixed + (L/period)·delta. We lower g ∈ {2, 4} groups (or
    {1, 2} when a group is multiple layers) and the dry-run fits the line
    with the non-negative estimator (see Cell.cost_cells). kv_chunk is
    raised to seq_len so the attention kv scan is also unrolled (single
    chunk) inside the cost cells.
    """
    period = cfg.global_every or 1
    if cfg.n_layers % period or cfg.n_layers < 2 * period:
        period = 1
    G = cfg.n_layers // period
    mults = (1, 2) if period > 1 else (2, 4)
    if G <= mults[1]:
        return [], float(G)
    seq = shape.seq_len or cfg.kv_chunk
    out = []
    for mult in mults:
        sub_cfg = dataclasses.replace(
            cfg,
            n_layers=mult * period,
            unroll_layers=True,
            kv_chunk=max(seq, cfg.kv_chunk),
        )
        sub_spec = dataclasses.replace(spec, make_config=lambda s=None, c=sub_cfg: c)
        out.append((_lm_cell(sub_spec, shape, mesh, _with_cost_cells=False), float(mult)))
    return out, float(G)


def _lm_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, dtype=BF16,
    _with_cost_cells: bool = True, optimized: bool = False,
) -> Cell:
    from repro.models.transformer_lm import (
        lm_decode_step,
        lm_init_cache,
        lm_loss,
        lm_param_shapes,
        lm_prefill,
    )

    cfg = spec.make_config(shape)
    da = data_axes(mesh)
    if optimized:
        # The §Perf findings as defaults: hierarchical MoE dispatch (T1),
        # remat for train (T2), donation handled below.
        n_data = int(np.prod([mesh.shape[a] for a in da]))
        kw = {}
        if cfg.is_moe:
            kw["moe_groups"] = n_data
        if shape.kind == "train":
            kw["remat"] = True
        if kw:
            cfg = dataclasses.replace(cfg, **kw)
    policy = sh.lm_policy(mesh, cfg)
    cost_cells, cost_groups = (
        _lm_cost_cells(spec, shape, mesh, cfg) if _with_cost_cells else (None, 1.0)
    )
    params_abs = jax.tree_util.tree_map(
        lambda l: _sds(l.shape, dtype), lm_param_shapes(cfg)
    )
    p_specs = sh.lm_param_specs(params_abs, cfg, mesh)
    p_shard = sh.tree_named(mesh, p_specs)

    if shape.kind == "train":
        opt = adamw(lr=3e-4)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_shard = sh.tree_named(mesh, _opt_specs(opt_abs, p_specs))
        tok_shard = sh.named(mesh, P(da, None))

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg, policy)
            new_params, new_opt = opt.update(grads, opt_state, params)
            return new_params, new_opt, loss

        tokens = _sds((shape.global_batch, shape.seq_len + 1), I32)
        return Cell(
            spec.arch_id, shape.name, "train_step",
            train_step,
            (params_abs, opt_abs, tokens),
            (p_shard, o_shard, tok_shard),
            (p_shard, o_shard, sh.named(mesh, P())),
            model_flops=6.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len,
            cost_cells=cost_cells,
            cost_groups=cost_groups,
            donate_argnums=(0, 1) if optimized else (),
        )

    if shape.kind == "prefill":
        tok_shard = sh.named(mesh, P(da, None))

        def prefill_step(params, tokens):
            return lm_prefill(params, tokens, cfg, policy)

        tokens = _sds((shape.global_batch, shape.seq_len), I32)
        return Cell(
            spec.arch_id, shape.name, "serve_step",
            prefill_step,
            (params_abs, tokens),
            (p_shard, tok_shard),
            sh.named(mesh, P(da, "model")),
            model_flops=2.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len,
            cost_cells=cost_cells,
            cost_groups=cost_groups,
        )

    # decode: one new token with a KV cache of seq_len.
    cache_abs = _abstract_tree(
        jax.eval_shape(lambda: lm_init_cache(cfg, shape.global_batch, shape.seq_len, dtype))
    )
    cspec = sh.cache_spec(cfg, shape, mesh)
    c_shard = jax.tree_util.tree_map(lambda _: sh.named(mesh, cspec), cache_abs)
    n_data = int(np.prod([mesh.shape[a] for a in da]))
    tok_spec = P(da) if shape.global_batch % n_data == 0 and shape.global_batch >= n_data else P()

    def decode_step(params, cache, token, pos):
        return lm_decode_step(params, cache, token, pos, cfg, policy)

    token = _sds((shape.global_batch,), I32)
    pos = _sds((), I32)
    return Cell(
        spec.arch_id, shape.name, "serve_step",
        decode_step,
        (params_abs, cache_abs, token, pos),
        (p_shard, c_shard, sh.named(mesh, tok_spec), sh.named(mesh, P())),
        (sh.named(mesh, P(tok_spec[0] if len(tok_spec) else None, "model")), c_shard),
        model_flops=2.0 * cfg.active_param_count() * shape.global_batch,
        note=f"KV cache {shape.seq_len} tokens, spec {cspec}",
        cost_cells=cost_cells,
        cost_groups=cost_groups,
        donate_argnums=(1,) if optimized else (),   # in-place cache update
    )


def _opt_specs(opt_abs, p_specs):
    """AdamW state {m, v, step}: m/v mirror param specs; step replicated."""
    del opt_abs
    return {"m": p_specs, "v": p_specs, "step": P()}


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


def _gnn_params(arch_id: str, cfg, dtype):
    key = jax.random.PRNGKey(0)
    if arch_id == "egnn":
        from repro.models.egnn import egnn_init

        return jax.eval_shape(lambda k: egnn_init(k, cfg, dtype), key)
    if arch_id == "graphcast":
        from repro.models.graphcast import graphcast_init

        return jax.eval_shape(lambda k: graphcast_init(k, cfg), key)     # float32 only
    if arch_id == "equiformer-v2":
        from repro.models.equiformer_v2 import equiformer_init

        return jax.eval_shape(lambda k: equiformer_init(k, cfg, dtype), key)
    if arch_id == "pna":
        from repro.models.pna import pna_init

        return jax.eval_shape(lambda k: pna_init(k, cfg, dtype), key)
    if arch_id == "coin_gcn":
        from repro.models.gcn import gcn_init

        return jax.eval_shape(lambda k: gcn_init(k, cfg, dtype), key)
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
    the coin_gcn aggregation term to the blocked cost model so hillclimb and
    the dry-run see the kernel's real nnz_blocks·B²·F work.
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
    d = getattr(cfg, "d_hidden", 512)
    return 2.0 * L * (n * d * d + e * d)


def _sampled_edges(shape: ShapeSpec) -> int:
    e, frontier = 0, shape.batch_nodes
    for f in shape.fanout:
        e += frontier * f
        frontier *= f
    return e


def _shape_halo_plan(n: int, e: int, k: int, pods: int = 1):
    """Cached HaloPlan for the (n, e) shape-statistics synthetic graph.

    Abstract cells have no real graph — like the rest of the dry-run they run
    on the deterministic exact-count synthetic (DESIGN.md §5), partitioned
    with the locality-seeking BFS+refine that keeps export sets small
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
    materialized for abstract cells. A legacy single-table record (no
    "interior" key, `plan_blocked_shape`) sizes just the combined triple."""
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
            if "interior" in bsr_stats:
                parts = (("interior", "bsr_"), ("boundary", "bsr_b"))
                tables = [(bsr_stats[tag], prefix) for tag, prefix in parts]
            else:
                tables = [(bsr_stats, "bsr_")]
            for st, prefix in tables:
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


def _gnn_halo_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, cfg, cost_cells, dtype=F32,
    payload: str | None = None,
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
    policy = sh.gnn_policy(mesh, batched=False, comm="halo", halo_payload=payload)
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

    params_abs = _gnn_params(spec.arch_id, cfg, dtype)
    p_specs = sh.replicated_specs(params_abs)
    p_shard = sh.tree_named(mesh, p_specs)
    batch_abs = _gnn_halo_batch_abstract(spec.arch_id, shape, cfg, plan, bsr_stats)
    batch_spec = {
        kk: sh.named(mesh, P(spec_axes, *([None] * (len(v.shape) - 1))))
        for kk, v in batch_abs.items()
    }
    total_loss = halo_loss_fn(spec.arch_id, cfg, mesh, policy)

    opt = adamw(lr=1e-3)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = sh.tree_named(mesh, _opt_specs(opt_abs, p_specs))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(total_loss)(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    note = (
        f"full graph (hier halo pods={pods} k={k} s_loc={plan.s_loc} "
        f"s_rem={plan.s_rem} n_local={plan.n_local})"
        if hier else
        f"full graph (halo k={k} s_max={plan.s_max} n_local={plan.n_local})"
    )
    if bsr_stats is not None:
        note += (
            f" bsr nnzb={bsr_stats['nnz_blocks']}"
            f" (int={bsr_stats['interior']['nnz_blocks']}"
            f" bnd={bsr_stats['boundary']['nnz_blocks']})"
            f" padfrac={bsr_stats['padded_tile_fraction']:.2f}"
        )
    if payload:
        note += f" payload={payload}"
    return Cell(
        spec.arch_id, shape.name, "train_step",
        train_step,
        (params_abs, opt_abs, batch_abs),
        (p_shard, o_shard, batch_spec),
        (p_shard, o_shard, sh.named(mesh, P())),
        model_flops=_gnn_flops(spec.arch_id, shape, cfg, bsr_stats) * 3.0,
        note=note,
        cost_cells=cost_cells,
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
    params_abs = _gnn_params(spec.arch_id, cfg, F32)
    p_specs = sh.replicated_specs(params_abs)
    p_shard = sh.tree_named(mesh, p_specs)
    batch_abs = _graphcast_batch_abstract(cfg, (n_data,))
    batch_spec = jax.tree_util.tree_map(
        lambda l: sh.named(mesh, P(da, *([None] * (len(l.shape) - 1)))), batch_abs)
    loss_fn = gnn_loss_fn(spec.arch_id, cfg, NO_POLICY)

    def total_loss(params, batch):
        return jnp.mean(jax.vmap(lambda b: loss_fn(params, b))(batch))

    opt = adamw(lr=1e-3)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = sh.tree_named(mesh, _opt_specs(opt_abs, p_specs))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(total_loss)(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    return Cell(
        spec.arch_id, shape.name, "train_step",
        train_step,
        (params_abs, opt_abs, batch_abs),
        (p_shard, o_shard, batch_spec),
        (p_shard, o_shard, sh.named(mesh, P())),
        model_flops=_gnn_flops(spec.arch_id, shape, cfg) * 3.0 * n_data,
        note=f"data parallel, one example per shard x{n_data}",
    )


def _gnn_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, dtype=F32,
    _as_cost_cell: bool = False, comm: str | None = None, optimized: bool = False,
    payload: str | None = None,
) -> Cell:
    import dataclasses as dc

    cfg = spec.make_config(shape)
    if spec.arch_id == "graphcast":
        return _graphcast_cell(spec, shape, mesh, cfg)
    if (
        optimized and spec.arch_id == "coin_gcn" and shape.batch_nodes is None
        and comm != "broadcast"
    ):
        # §Perf: full-graph GCN aggregation on the ragged blocked MXU kernel
        # (DESIGN.md §2) instead of the segment-sum reference. Halo cells
        # only — they thread the per-shard blocked adjacency through the
        # batch; the broadcast escape hatch has no adjacency to feed bsr.
        cfg = dc.replace(cfg, backend="bsr")
    cost_cells = None
    big = (shape.n_edges or 0) > 2_000_000
    if (
        spec.arch_id == "equiformer-v2" and big and not _as_cost_cell
        and getattr(cfg, "edge_chunk", None) is None
    ):
        # Real program: 64 rolled chunks bound the (chunk, K, C) irrep tensor.
        # Cost cell: the unchunked variant — its HLO is fully counted by
        # cost_analysis (the rolled chunk scan body would be counted once).
        flat_spec = dc.replace(spec, make_config=lambda s=None, c=cfg: c)
        cost_cells = [
            (_gnn_cell(flat_spec, shape, mesh, dtype, _as_cost_cell=True, comm=comm), 1.0)
        ]
        cfg = dc.replace(cfg, edge_chunk=-(-shape.n_edges // 64))
    da = data_axes(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in da]))
    msize = mesh.shape["model"]
    sampled = shape.batch_nodes is not None
    if comm is None:
        comm = "broadcast" if sampled else "halo"
    if not sampled and comm == "halo":
        return _gnn_halo_cell(spec, shape, mesh, cfg, cost_cells, dtype, payload=payload)
    n_blocks = n_data if sampled else None
    policy = NO_POLICY if sampled else sh.gnn_policy(mesh, batched=False, comm="broadcast")

    params_abs = _gnn_params(spec.arch_id, cfg, dtype)
    p_specs = sh.replicated_specs(params_abs)
    p_shard = sh.tree_named(mesh, p_specs)
    loss_fn = gnn_loss_fn(
        spec.arch_id, cfg, policy, n_loss_nodes=shape.batch_nodes if sampled else None
    )
    batch_abs = _gnn_batch_abstract(spec.arch_id, shape, cfg, n_blocks, pad_mult=msize)

    if sampled:
        batch_spec = jax.tree_util.tree_map(
            lambda l: sh.named(mesh, P(da, *([None] * (len(l.shape) - 1)))), batch_abs
        )

        def total_loss(params, batch):
            losses = jax.vmap(lambda b: loss_fn(params, b))(batch)
            return jnp.mean(losses)
    else:
        def node_or_edge_spec(l):
            # Shard the big axis (nodes or edges) over `model`.
            return sh.named(mesh, P("model", *([None] * (len(l.shape) - 1))))

        batch_spec = jax.tree_util.tree_map(node_or_edge_spec, batch_abs)
        total_loss = loss_fn

    opt = adamw(lr=1e-3)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = sh.tree_named(mesh, _opt_specs(opt_abs, p_specs))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(total_loss)(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

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
    return Cell(
        spec.arch_id, shape.name, "train_step",
        train_step,
        (params_abs, opt_abs, batch_abs),
        (p_shard, o_shard, batch_spec),
        (p_shard, o_shard, sh.named(mesh, P())),
        model_flops=flops,
        note="sampled blocks ×%d" % (n_blocks or 1) if sampled else "full graph (broadcast)",
        cost_cells=cost_cells,
        comm=None if sampled else "broadcast",
    )


# ===================================================================== recsys
def _recsys_cell(spec: ArchSpec, shape: ShapeSpec, mesh, dtype=F32) -> Cell:
    from repro.models.deepfm import (
        deepfm_forward,
        deepfm_init,
        deepfm_loss,
        deepfm_retrieval,
    )

    cfg = spec.make_config(shape)
    da = data_axes(mesh)
    policy = sh.recsys_policy(mesh)
    params_abs = jax.eval_shape(lambda k: deepfm_init(k, cfg, dtype), jax.random.PRNGKey(0))
    p_specs = sh.recsys_param_specs(params_abs)
    p_shard = sh.tree_named(mesh, p_specs)
    mlp_flops = 2.0 * sum(
        a * b for a, b in zip(
            (cfg.n_fields * cfg.embed_dim, *cfg.mlp_dims), (*cfg.mlp_dims, 1)
        )
    )
    per_ex = mlp_flops + 4.0 * cfg.n_fields * cfg.embed_dim

    if shape.kind == "train":
        opt = adamw(lr=1e-3)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_shard = sh.tree_named(mesh, _opt_specs(opt_abs, p_specs))

        def train_step(params, opt_state, ids, labels):
            loss, grads = jax.value_and_grad(deepfm_loss)(params, ids, labels, cfg, policy)
            new_params, new_opt = opt.update(grads, opt_state, params)
            return new_params, new_opt, loss

        ids = _sds((shape.batch, cfg.n_fields), I32)
        labels = _sds((shape.batch,), F32)
        bspec = sh.named(mesh, P(da, None))
        return Cell(
            spec.arch_id, shape.name, "train_step",
            train_step,
            (params_abs, opt_abs, ids, labels),
            (p_shard, o_shard, bspec, sh.named(mesh, P(da))),
            (p_shard, o_shard, sh.named(mesh, P())),
            model_flops=3.0 * per_ex * shape.batch,
        )

    if shape.kind == "retrieval":
        def retrieval_step(params, user_ids, cand_ids):
            return deepfm_retrieval(params, user_ids, cand_ids, cfg, policy)

        user = _sds((shape.batch, cfg.n_fields), I32)
        cands = _sds((shape.batch, shape.n_candidates), I32)
        return Cell(
            spec.arch_id, shape.name, "serve_step",
            retrieval_step,
            (params_abs, user, cands),
            (p_shard, sh.named(mesh, P(None, None)), sh.named(mesh, P(None, "model"))),
            sh.named(mesh, P(None, "model")),
            model_flops=2.0 * shape.batch * shape.n_candidates * cfg.d_tower,
        )

    def serve_step(params, ids):
        return deepfm_forward(params, ids, cfg, policy)

    ids = _sds((shape.batch, cfg.n_fields), I32)
    big = shape.batch >= int(np.prod([mesh.shape[a] for a in da]))
    bspec = sh.named(mesh, P(da, None) if big else P(None, None))
    return Cell(
        spec.arch_id, shape.name, "serve_step",
        serve_step,
        (params_abs, ids),
        (p_shard, bspec),
        sh.named(mesh, P(da) if big else P()),
        model_flops=per_ex * shape.batch,
    )


# ==================================================================== factory
def build_cell(
    spec: ArchSpec, shape: ShapeSpec, mesh, optimized: bool = False,
    comm: str | None = None, payload: str | None = None,
) -> Cell:
    """optimized=True applies the §Perf findings (hierarchical MoE dispatch,
    remat on train, param/opt/cache donation) — the beyond-paper variants
    recorded separately from the baselines in EXPERIMENTS.md.

    comm selects the full-graph GNN communication schedule: None → the
    family default ("halo" for full-graph cells, DESIGN.md §8);
    "broadcast" → the paper-faithful layer-output all-gather escape hatch.
    Non-GNN families ignore it. For coin_gcn full-graph cells optimized=True
    also switches the aggregation to ``backend="bsr"`` (the ragged blocked
    MXU kernel, with the per-shard split blocked adjacency threaded through
    the halo batch). payload selects the halo wire format (None/"fp32" |
    "bf16" | "int8" — quantized boundary rows, dequantized on receive;
    docs/communication.md "Overlapped schedule"); halo cells only."""
    if spec.family == "lm":
        return _lm_cell(spec, shape, mesh, optimized=optimized)
    if spec.family == "gnn":
        return _gnn_cell(spec, shape, mesh, comm=comm, optimized=optimized, payload=payload)
    if spec.family == "recsys":
        return _recsys_cell(spec, shape, mesh)
    raise KeyError(spec.family)
