"""Distributed full-graph GCN training over the DEFAULT halo comm path.

Demonstrates the PR-2 communication stack end to end (DESIGN.md §8): a
Cora-stats synthetic graph is partitioned across every visible device
(BFS + refinement, the locality lever that keeps export sets small), the
cached `HaloPlan` relocates it into blocked per-device layout, and each GCN
layer's aggregation exchanges only boundary rows via
`policy.neighbor_table` inside `shard_map` — `k·s_max` received rows per
device instead of the broadcast schedule's `(k−1)·n_local`. Training runs
on the production substrate (`Trainer`: jitted step, checkpointing,
straggler monitor) and prints the plan-cache hit count: one relocation
serves every layer of every step.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/train_distributed_gcn.py [--steps 60]

Runs on any device count (including 1, where the halo degenerates to an
empty exchange). ``--pods 2`` switches to the hierarchical (pod, model)
schedule (docs/communication.md): the mesh becomes 2-D, the plan splits
each device's boundary set into intra-/inter-pod tiers, and the exchange
runs in two phases — the printout shows how few rows cross the expensive
inter-pod fabric vs the flat plan.

``--trace out.json`` / ``--metrics out.json`` (docs/observability.md) turn
on the `repro.obs` telemetry: the metrics snapshot mirrors the plan's wire
accounting and cache stats, and the trace holds the training loop's spans
(``train.step`` ⊃ ``train.dispatch``, ``train.sync``).
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.partition import partition_graph
from repro.dist.halo import (
    get_halo_plan,
    node_mask,
    plan_cache_stats,
    relocate_node_array,
    restore_node_array,
)
from repro.dist.policy import ShardingPolicy
from repro.graph.generators import make_dataset
from repro.launch.mesh import make_halo_mesh, make_mesh
from repro.launch.obsflags import add_obs_args, obs_session
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init
from repro.obs import metrics as obs_metrics
from repro.train.loop import Trainer, TrainerConfig
from repro.train.optimizer import adamw


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--pods", type=int, default=1,
                    help="pods for the hierarchical (pod, model) halo schedule "
                         "(must divide the device count; 1 = flat single-axis)")
    add_obs_args(ap)
    args = ap.parse_args()
    with obs_session(args):
        run(args)


def run(args) -> None:
    k = jax.device_count()
    pods = args.pods
    if pods < 1 or k % pods:
        raise SystemExit(f"--pods {pods} must divide the device count {k}")
    hier = pods > 1
    if hier:
        axes = ("pod", "model")
        mesh = make_halo_mesh(pods, k // pods)
        print(f"devices: {k} (mesh {pods}×{k // pods}, axes {axes})")
    else:
        axes = ("model",)
        mesh = make_mesh((k,), axes)
        print(f"devices: {k} (mesh axis 'model')")

    # ---- graph → partition → cached halo plan --------------------------------
    spec, g = make_dataset("cora", reduced=True)
    gs = g.symmetrized().with_self_loops()
    w = gs.sym_normalized_weights()
    part = partition_graph(gs.n_nodes, gs.edge_index, k, method="bfs", seed=0, refine=True)
    pods_kw = {"pods": pods} if hier else {}
    plan = get_halo_plan(part, gs.edge_index, w, **pods_kw)   # miss: builds the relocation
    plan = get_halo_plan(part, gs.edge_index, w, **pods_kw)   # hit: every reuse is free
    print(
        f"graph: {spec.name} n={gs.n_nodes} e={gs.n_edges} → k={plan.k} "
        f"n_local={plan.n_local} "
        + (f"s_loc={plan.s_loc} s_rem={plan.s_rem}" if hier else f"s_max={plan.s_max}")
    )
    if plan.k > 1:
        print(
            f"wire/device/layer: halo {plan.halo_rows_per_device} rows vs "
            f"broadcast {plan.broadcast_rows_per_device} rows "
            f"({plan.wire_fraction():.3f}× — DESIGN.md §8)"
        )
    if hier:
        print(
            f"inter-pod crossing/device/layer: {plan.inter_pod_rows_crossing} rows "
            f"hierarchical vs {plan.flat_inter_pod_rows_crossing} flat "
            "(docs/communication.md)"
        )

    # ---- blocked batch (static across steps: full-graph training) ------------
    if hier:
        sloc, srem, sl, rl, ew = plan.device_arrays()
        send = {"send_loc": sloc, "send_rem": srem}
    else:
        si, sl, rl, ew = plan.device_arrays()
        send = {"send_idx": si}
    batch = {
        "feats": jnp.asarray(relocate_node_array(plan, g.features.astype(np.float32))),
        "labels": jnp.asarray(relocate_node_array(plan, g.labels.astype(np.int32))),
        "mask": jnp.asarray(node_mask(plan)),
        **send, "senders": sl, "receivers": rl, "edge_w": ew,
    }
    keys = sorted(batch)
    spec_axes = axes if hier else "model"

    cfg = GCNConfig(layer_dims=(spec.n_features, spec.hidden, spec.n_labels))
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    policy = ShardingPolicy(comm="halo", halo_axes=axes if hier else None)

    def bind(b):
        if hier:
            return policy.bind_halo(send_loc=b["send_loc"], send_rem=b["send_rem"])
        return policy.bind_halo(b["send_idx"])

    def loss_fn(params, batch):
        def body(*args):
            b = {kk: a[0] for kk, a in zip(keys, args)}
            pol = bind(b)
            logits = gcn_forward(
                params, b["feats"], b["senders"], b["receivers"], b["edge_w"], cfg, pol
            ).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, b["labels"][:, None], axis=-1)[:, 0]
            wsum = ((lse - gold) * b["mask"]).sum()
            wcnt = b["mask"].sum()
            loss = jax.lax.psum(wsum, spec_axes) / jnp.maximum(
                jax.lax.psum(wcnt, spec_axes), 1.0
            )
            return loss[None]

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(spec_axes),) * len(keys), out_specs=P(spec_axes),
            check_vma=False,
        )
        return f(*[batch[kk] for kk in keys]).mean()

    # ---- production substrate: Trainer (jit step, ckpt, straggler monitor) ---
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="coin_ckpt_")
    tr = Trainer(
        loss_fn, adamw(1e-2), params,
        TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=50, log_every=20),
    )
    resumed = tr.resume()
    print(f"checkpoints → {ckpt_dir} (resumed={resumed}, step={tr.step})")
    losses = tr.fit(iter(lambda: batch, None), max_steps=args.steps)

    # ---- evaluate through the same halo path ---------------------------------
    def fwd(batch):
        def body(*args):
            b = {kk: a[0] for kk, a in zip(keys, args)}
            pol = bind(b)
            return gcn_forward(
                tr.params, b["feats"], b["senders"], b["receivers"], b["edge_w"], cfg, pol
            )[None]

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(spec_axes),) * len(keys), out_specs=P(spec_axes),
            check_vma=False,
        )
        return f(*[batch[kk] for kk in keys])

    logits = restore_node_array(plan, np.asarray(fwd(batch)))
    acc = float((logits.argmax(-1) == g.labels).mean())
    stats = plan_cache_stats()
    print(f"done: step={tr.step} loss {losses[0]:.4f} → {losses[-1]:.4f} acc={acc:.3f}; "
          f"stragglers observed: {len(tr.straggler_events)}")
    print(f"plan cache: {stats['hits']} hits / {stats['misses']} misses "
          f"({stats['size']} cached) — one relocation serves all layers/steps")
    assert losses[-1] < losses[0], "training must make progress"
    assert stats["hits"] >= 1 and stats["misses"] >= 1

    # ---- telemetry: mirror the accounting ------------------------------------
    if obs_metrics.enabled():
        from repro.obs.instrument import observe_plan_cache, record_exchange

        record_exchange(plan, int(batch["feats"].shape[-1]))
        observe_plan_cache()


if __name__ == "__main__":
    main()
