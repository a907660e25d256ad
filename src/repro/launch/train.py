"""Training driver: ``--arch <id>`` selects any assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch pna --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch coin_gcn --shape nell --steps 5

Runs the REDUCED config on the local device(s) unless ``--shape`` names a
registry shape of the arch: coin_gcn then trains its published config on
that Table-I dataset at full size (`repro.graph.generators.make_dataset`),
graphcast (``--shape era5_1deg``) GraphCast_small on its 1° grid and
multimesh, one seeded synthetic example per step.
The other archs train their reduced configs. The driver wires the complete
substrate: synthetic data stream → jitted train step → AdamW →
checkpointing → straggler monitor, and resumes from the latest checkpoint
on restart.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_arch
from repro.graph.generators import make_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.obsflags import add_obs_args, obs_session
from repro.train.loop import Trainer, TrainerConfig
from repro.train.optimizer import adamw


def _lm_setup(spec, batch=4, seq=64):
    from repro.models.transformer_lm import lm_init, lm_loss
    from repro.train.data import ShardedStream, token_batch_fn

    cfg = spec.make_reduced()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    stream = ShardedStream(token_batch_fn(cfg.vocab, seq), global_batch=batch, seed=0)

    def batches():
        for b in stream:
            yield jnp.asarray(b)

    return params, (lambda p, b: lm_loss(p, b, cfg)), batches


def _shape_batch(spec, shape: str):
    """coin_gcn's published config on the Table-I dataset ``shape`` at full
    size, with self-loops and Kipf–Welling weights. Returns (cfg, graph,
    batch); features are cast to fp32 on the host, so the float16 that
    `make_dataset` emits for large sets never reaches the device."""
    if spec.arch_id != "coin_gcn":
        raise SystemExit(f"--shape trains coin_gcn on a Table-I dataset or graphcast on "
                         f"its grid; {spec.arch_id} has no dataset for {shape!r}")
    cfg = spec.make_config(spec.shapes[shape])
    _, g = make_dataset(shape)
    if g.features.shape[1] != cfg.layer_dims[0]:
        raise ValueError(f"{shape}: features are {g.features.shape[1]} wide, "
                         f"the config expects {cfg.layer_dims[0]}")
    g = g.with_self_loops()
    batch = {
        "feats": jnp.asarray(g.features.astype(np.float32)),
        "senders": jnp.asarray(g.edge_index[0]),
        "receivers": jnp.asarray(g.edge_index[1]),
        "edge_weight": jnp.asarray(g.sym_normalized_weights()),
        "labels": jnp.asarray(g.labels),
        "label_mask": jnp.ones(g.n_nodes),
    }
    return cfg, g, batch


def _graphcast_batches(cfg, n_examples: int = 4, seed: int = 0):
    """GraphCast batches over ``cfg``'s graphs, cycling ``n_examples``
    synthetic examples: inputs N(0, 1), the target the state at t plus
    N(0, 1) (fields taken as normalized)."""
    from repro.models.graphcast import graphcast_graph

    graph = {k: jnp.asarray(v) for k, v in graphcast_graph(cfg).arrays().items()}
    n_grid = graph["grid_nodes"].shape[0]
    rng = np.random.default_rng(seed)
    examples = []
    for _ in range(n_examples):
        x = rng.standard_normal((n_grid, cfg.d_grid_in), np.float32)
        state = x[:, (cfg.n_input_steps - 1) * cfg.n_vars: cfg.n_input_steps * cfg.n_vars]
        y = state + rng.standard_normal(state.shape, np.float32)
        examples.append(dict(graph, grid_inputs=jnp.asarray(x), grid_target=jnp.asarray(y)))

    def batches():
        while True:
            yield from examples

    return batches


def _gnn_setup(spec, relocalize_threshold: float = 0.0, shape: str | None = None):
    from repro.graph.generators import citation_like
    from repro.launch.steps import gnn_loss_fn
    from repro.dist.policy import NO_POLICY

    if spec.arch_id == "graphcast":
        if relocalize_threshold > 0:
            raise SystemExit("--relocalize-threshold churns a generic graph; graphcast's "
                             "graphs are fixed by its grid and mesh")
        cfg = spec.make_reduced() if shape is None else spec.make_config(spec.shapes[shape])
        return (_init_gnn(spec.arch_id, cfg), gnn_loss_fn(spec.arch_id, cfg, NO_POLICY),
                _graphcast_batches(cfg))
    if shape is not None:
        cfg, g, base = _shape_batch(spec, shape)
    else:
        cfg = spec.make_reduced()
        d_in = getattr(cfg, "d_in", None) or getattr(cfg, "input_dim", 8)
        g = citation_like(256, 1024, seed=0)
        rng = np.random.default_rng(0)
        if spec.arch_id == "coin_gcn":
            d_in = cfg.layer_dims[0]
        base = {
            "feats": jnp.asarray(rng.standard_normal((g.n_nodes, d_in)), jnp.float32),
            "senders": jnp.asarray(g.edge_index[0]),
            "receivers": jnp.asarray(g.edge_index[1]),
        }
        if spec.arch_id in ("egnn", "equiformer-v2"):
            base["pos"] = jnp.asarray(rng.standard_normal((g.n_nodes, 3)), jnp.float32)
        if spec.arch_id == "coin_gcn":
            base["edge_weight"] = jnp.ones(g.n_edges)
            base["labels"] = jnp.asarray(g.labels)
            base["label_mask"] = jnp.ones(g.n_nodes)
        else:
            base["target"] = jnp.asarray(
                rng.standard_normal((g.n_nodes, cfg.d_out)) * 0.1, jnp.float32)

    loss = gnn_loss_fn(spec.arch_id, cfg, NO_POLICY)
    params = _init_gnn(spec.arch_id, cfg)

    if relocalize_threshold <= 0:
        def batches():
            while True:
                yield base

        return params, loss, batches

    # --relocalize-threshold: churn the training graph while a
    # drift-triggered RelocalizePolicy maintains the planner's locality
    # order online (docs/communication.md §8). Edge COUNT stays constant
    # (delete m, insert m) so the jitted step never retraces.
    from repro.core.partition import partition_graph
    from repro.dist.delta import DeltaPlanner, GraphDelta, RelocalizePolicy

    part = partition_graph(g.n_nodes, g.edge_index, 4, "bfs", seed=0, refine=True)
    planner = DeltaPlanner(
        part, g.edge_index, graph_key=f"launch-train-{spec.arch_id}",
        relocalize_policy=RelocalizePolicy(
            threshold=relocalize_threshold, patience=2, cooldown=3))
    churn = np.random.default_rng(1)

    def batches():
        step = 0
        while True:
            yield base
            step += 1
            if step % 10:
                continue
            ei = planner.edge_index()
            m = max(ei.shape[1] // 100, 2)
            drop = churn.choice(ei.shape[1], m, replace=False)
            mem = churn.choice(g.n_nodes, 16, replace=False)
            s = mem[churn.integers(0, mem.size, m)]
            d = mem[churn.integers(0, mem.size, m)]
            bad = s == d
            d[bad] = mem[(np.searchsorted(np.sort(mem), d[bad]) + 1) % mem.size]
            rep = planner.apply(GraphDelta(
                edge_inserts=np.stack([s, d]), edge_deletes=ei[:, drop]))
            if rep["relocalized"] is not None:
                r = rep["relocalized"]
                print(f"  relocalize @ step {step}: executed tiles "
                      f"{r['executed_tiles_before']} → {r['executed_tiles_after']}")
            new_ei = planner.edge_index()
            base["senders"] = jnp.asarray(new_ei[0].astype(np.int32))
            base["receivers"] = jnp.asarray(new_ei[1].astype(np.int32))
            if "edge_weight" in base:
                base["edge_weight"] = jnp.asarray(planner.edge_weights())

    return params, loss, batches


def _init_gnn(arch_id, cfg):
    key = jax.random.PRNGKey(0)
    if arch_id == "egnn":
        from repro.models.egnn import egnn_init

        return egnn_init(key, cfg)
    if arch_id == "graphcast":
        from repro.models.graphcast import graphcast_init

        return graphcast_init(key, cfg)
    if arch_id == "equiformer-v2":
        from repro.models.equiformer_v2 import equiformer_init

        return equiformer_init(key, cfg)
    if arch_id == "pna":
        from repro.models.pna import pna_init

        return pna_init(key, cfg)
    from repro.models.gcn import gcn_init

    return gcn_init(key, cfg)


def _recsys_setup(spec, batch=256):
    from repro.models.deepfm import deepfm_init, deepfm_loss
    from repro.train.data import ShardedStream, click_batch_fn

    cfg = spec.make_reduced()
    params = deepfm_init(jax.random.PRNGKey(0), cfg)
    stream = ShardedStream(
        click_batch_fn(cfg.n_fields, cfg.rows_per_field), global_batch=batch, seed=0
    )

    def batches():
        for b in stream:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    return params, (lambda p, b: deepfm_loss(p, b["ids"], b["labels"], cfg)), batches


def main(argv=None) -> list[float]:
    """Train ``--steps`` steps; returns the per-step losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--shape", default=None,
                    help="registry shape of the arch to train at full size "
                         "(coin_gcn: a Table-I dataset, e.g. nell; graphcast: era5_1deg)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--relocalize-threshold", type=float, default=0.0,
                    help="drift ratio beyond which the churned training graph "
                         "re-localizes online (0 = static graph; gnn only)")
    add_obs_args(ap)
    args = ap.parse_args(argv)

    spec = get_arch(args.arch)
    if args.shape is not None:
        if spec.family != "gnn":
            ap.error(f"--shape is for GNN archs; {args.arch} is {spec.family}")
        if args.shape not in spec.shapes:
            ap.error(f"--shape {args.shape!r}: {args.arch} has {sorted(spec.shapes)}")
    use_compile_cache()
    setup = {"lm": _lm_setup, "gnn": _gnn_setup, "recsys": _recsys_setup}[spec.family]
    with obs_session(args):
        if spec.family == "gnn":
            params, loss_fn, batches = _gnn_setup(
                spec, relocalize_threshold=args.relocalize_threshold, shape=args.shape)
        else:
            params, loss_fn, batches = setup(spec)
        tr = Trainer(
            loss_fn,
            adamw(args.lr),
            params,
            TrainerConfig(
                ckpt_dir=args.ckpt_dir, log_every=10, compress_grads=args.compress_grads
            ),
        )
        if args.ckpt_dir:
            tr.resume()
        losses = tr.fit(batches(), max_steps=args.steps)
        print(f"{args.arch}: loss {losses[0]:.4f} → {losses[-1]:.4f} over {len(losses)} steps")
        dts = tr.step_seconds
        if len(dts) > 1:
            print(f"  step seconds: first {dts[0]:.3f} (compile included), "
                  f"median of the rest {float(np.median(dts[1:])):.4f}")
    return losses


if __name__ == "__main__":
    main()
