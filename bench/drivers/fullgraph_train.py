"""Full-graph training: `Trainer.fit` over `gnn_loss_fn`, one step per call.

Set-up builds the program's graph (self-loops, normalization, the node
order and blocked tables its backend needs), puts it on the chip, makes the
weights from ``--seed`` and builds one `Trainer`. That same trainer runs the
first ``check_steps`` steps (the first one compiles) and then the window:
every step of the window goes through the same call on the same batch.
Full-graph training has one batch, the whole graph, so every step covers
every node.

``step_s`` is the window's seconds over the steps completed in it.

``correct``: the plain reference (`bench.reference`) follows the first
``check_steps`` steps from the same weights, on its own copy of the graph in
the generated node order. Compared, each by its worst case: the loss of each
of those steps; per leaf, the norm of the first gradient as the optimizer got
it (Adam's first moment after one step over 1 − b1); per leaf, the norm of
the parameters' change over those steps, for the leaves that the reference's
gradient moves (`bench.compare.moving_leaves`).
"""
from __future__ import annotations

import gc
import itertools
import math
import time

import jax
import numpy as np

from bench import compare, data
from bench.device import device_facts, prng_key
from bench.reference import gcn as ref


def program_config(cfg: dict):
    from repro.core.quant import QuantConfig
    from repro.models.gcn import GCNConfig

    m = cfg["model"]
    quant = QuantConfig(weight_bits=m["weight_bits"], act_bits=m["act_bits"],
                        enabled=m["quant"], act_percentile=m["act_percentile"])
    return GCNConfig(layer_dims=tuple(m["layer_dims"]), dataflow=m["dataflow"],
                     quant=quant, backend=m["backend"])


def program_batch(raw: data.RawGraph, cfg: dict) -> dict:
    """The batch of `repro.launch.steps.gnn_loss_fn`, built by the program's
    own graph code (host numpy): self-loops, Kipf–Welling weights, and for
    ``node_order: bfs_locality`` the locality order and, for the bsr
    backend, the ragged blocked adjacency."""
    from repro.graph.structure import (GraphData, blocked_adjacency, locality_block_order,
                                       permute_edge_index, relocate_rows)

    m = cfg["model"]
    g = GraphData(n_nodes=raw.n_nodes, edge_index=np.stack([raw.senders, raw.receivers]),
                  features=raw.features, labels=raw.labels).with_self_loops()
    weight = g.sym_normalized_weights()
    edge_index, feats, labels = g.edge_index, g.features, g.labels
    if m["node_order"] == "bfs_locality":
        perm = locality_block_order(g.n_nodes, edge_index)
        edge_index = permute_edge_index(perm, edge_index)
        feats, labels = relocate_rows(perm, feats), relocate_rows(perm, labels)
    elif m["node_order"] != "generated":
        raise ValueError(f"unknown node_order {m['node_order']!r}")
    batch = {
        "feats": np.asarray(feats, np.float32),
        "senders": edge_index[0], "receivers": edge_index[1],
        "edge_weight": weight,
        "labels": np.asarray(labels, np.int32),
        "label_mask": np.ones(g.n_nodes, np.float32),
    }
    if m["backend"] == "bsr":
        ba = blocked_adjacency(g.n_nodes, edge_index, weight)
        batch.update(bsr_vals=ba.block_vals, bsr_cols=ba.block_cols, bsr_lens=ba.row_nnzb)
    return batch


def reference_data(raw: data.RawGraph) -> dict:
    s, r, w = ref.normalized_edges(raw.n_nodes, raw.senders, raw.receivers)
    return jax.device_put({
        "feats": raw.features, "senders": s, "receivers": r, "weight": w,
        "labels": raw.labels, "label_mask": np.ones(raw.n_nodes, np.float32)})


def _host(tree) -> dict:
    return {k: np.asarray(v) for k, v in tree.items()}


def prepare(cell) -> tuple[data.RawGraph, dict, float]:
    """The dataset, the program's batch on the chip, and the host seconds
    the program took to build that batch."""
    raw = data.make_graph(cell.config["dataset"])
    t0 = time.perf_counter()
    host_batch = program_batch(raw, cell.config)
    setup_graph_s = time.perf_counter() - t0
    return raw, jax.device_put(host_batch), setup_graph_s


def first_steps(cell, batch: dict, seed: int):
    """Weights from ``seed``, one `Trainer`, and its first ``check_steps``
    steps. Returns the trainer, what those steps gave (``losses``, first
    ``grads``, parameter ``change``) and the initial weights (host)."""
    from repro.dist.policy import NO_POLICY
    from repro.launch.steps import gnn_loss_fn
    from repro.train.loop import Trainer, TrainerConfig
    from repro.train.optimizer import adamw

    cfg, opt = cell.config, cell.traffic["optimizer"]
    params = ref.init_params(prng_key(seed, "weights"), cfg["model"]["layer_dims"])
    p0 = _host(params)
    trainer = Trainer(
        gnn_loss_fn(cfg["model"]["arch"], program_config(cfg), NO_POLICY),
        adamw(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
              weight_decay=opt["weight_decay"]),
        params, TrainerConfig(log_every=10**9))
    del params
    feed = itertools.repeat(batch)
    losses = trainer.fit(feed, max_steps=1)
    grads = {k: np.asarray(v) / (1.0 - opt["b1"]) for k, v in trainer.opt_state["m"].items()}
    losses += trainer.fit(feed, max_steps=int(cell.traffic["check_steps"]))
    change = {k: np.asarray(v) - p0[k] for k, v in trainer.params.items()}
    return trainer, {"losses": losses, "grads": grads, "change": change}, p0


def reference(cell, ref_data: dict, p0: dict, precision=ref.REFERENCE, step_fault=None) -> dict:
    """The reference's ``check_steps`` steps from ``p0``, as `first_steps`
    reports the program's."""
    losses, grads, p = ref.train(jax.device_put(p0), ref_data, _ref_model(cell.config),
                                 cell.traffic["optimizer"], int(cell.traffic["check_steps"]),
                                 precision, step_fault)
    return {"losses": losses, "grads": grads, "change": {k: p[k] - p0[k] for k in p0}}


def run(cell, seed: int, seconds: float, tracer, t_start: float, devices) -> dict:
    raw, batch, setup_graph_s = prepare(cell)
    trainer, got, p0 = first_steps(cell, batch, seed)
    # Set-up's garbage is collected here, and what it leaves is frozen: a
    # collection inside the window then scans only the window's objects.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    feed = itertools.repeat(batch)
    steps = failed = 0
    with tracer.window():
        w0 = time.perf_counter()
        while True:
            with tracer.annotate("train.step"):
                loss = trainer.fit(feed, max_steps=trainer.step + 1)
            steps += 1
            failed += int(not all(math.isfinite(x) for x in loss))
            window_s = time.perf_counter() - w0
            if window_s >= seconds:
                break
    facts = device_facts(devices)
    gc.unfreeze()
    del trainer, feed, batch
    gc.collect()

    readings = compare.train_readings(got, reference(cell, reference_data(raw), p0))
    return {
        "end_to_end": {"setup_s": setup_s, "step_s": window_s / steps},
        "attempted": steps, "failed": failed,
        "checks": compare.checks(readings, cell.config["limits"]["fullgraph_train"]),
        "device": facts,
        "counters": {"steps": steps, "window_s": window_s, "setup_graph_s": setup_graph_s,
                     "n_nodes": raw.n_nodes, "n_edges": raw.n_edges},
    }


def _ref_model(cfg: dict) -> dict:
    m = cfg["model"]
    return {k: m[k] for k in ("layer_dims", "weight_bits", "act_bits", "act_percentile", "quant")}
