"""GraphCast training: `Trainer.fit` over `gnn_loss_fn("graphcast", ...)`.

One-step (6 h) prediction, the first phase of GraphCast's curriculum, one
example per step (one device's share of GraphCast's batch of 32).

Set-up builds the program's graphs (`repro.graph.sphere`: the grid, the
multimesh, Grid2Mesh and Mesh2Grid with their features), checks their sizes
against the configuration's, puts them on the chip, makes ``examples``
seeded examples on the chip (inputs and targets; see `examples`), the
weights from ``--seed``, and one `Trainer`. That trainer runs the first
``check_steps`` steps (the first one compiles) and then the window; step i
takes example ``i % examples``, so no two steps in a row see the same
arrays.

``step_s`` is the window's seconds over the steps completed in it.

``correct``: the plain reference (`bench.reference.graphcast`) follows the
first ``check_steps`` steps from the same weights, on the same examples,
with its own node and edge features and loss weights built from the
program's connectivity. Compared as the full-graph cells compare
(`bench.compare.train_readings`): each step's loss, each leaf's first
gradient (Adam's first moment after one step over 1 − b1), each moving
leaf's change over the steps.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench.device import device_facts, prng_key
from bench.reference import graphcast as ref


def program_config(cfg: dict):
    from repro.models.graphcast import GraphCastConfig

    m = cfg["model"]
    return GraphCastConfig(
        resolution=m["resolution"], mesh_splits=m["mesh_splits"],
        mesh_min_level=m["mesh_min_level"], radius_fraction=m["radius_fraction"],
        d_latent=m["d_latent"], n_layers=m["n_layers"],
        surface_weights=tuple(m["surface_weights"]), n_atmos_vars=len(m["atmos_vars"]),
        pressure_levels=tuple(m["pressure_levels"]), n_input_steps=m["n_input_steps"],
        n_forcings=m["n_forcings"], n_static=m["n_static"])


def prepare(cell):
    """The program's graphs (host), their batch entries on the chip, and the
    host seconds the program took to build them."""
    from repro.models.graphcast import graphcast_graph

    t0 = time.perf_counter()
    graph = graphcast_graph(program_config(cell.config))
    setup_graph_s = time.perf_counter() - t0
    m = cell.config["model"]
    for k, v in graph.sizes.items():
        if v != m[k]:
            raise ValueError(f"the program's graph has {k} = {v}, the configuration {m[k]}")
    return graph, jax.device_put(graph.arrays()), setup_graph_s


def examples(cell, seed: int) -> list:
    """``(inputs, target)`` pairs on the chip: every input channel N(0, 1),
    the target the state at t plus N(0, 1), as normalized fields are."""
    m = cell.config["model"]
    n, n_vars, t = m["n_grid"], m["n_vars"], m["n_input_steps"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (n, m["d_grid_in"]), jnp.float32)
        return x, x[:, (t - 1) * n_vars: t * n_vars] + jax.random.normal(ky, (n, n_vars))

    key = prng_key(seed, "examples")
    return [make(jax.random.fold_in(key, i)) for i in range(int(cell.traffic["examples"]))]


def _flat(tree) -> dict:
    """A parameter tree as a host dict named by path (``embed/grid/mlp/l0/w``)."""
    return {"/".join(str(k.key) for k in path): np.asarray(v)
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def _step(trainer, batch) -> list:
    return trainer.fit((batch,), max_steps=trainer.step + 1)


def first_steps(cell, batches: list, seed: int):
    """Weights from ``seed``, one `Trainer`, and its first ``check_steps``
    steps. Returns the trainer, what those steps gave (``losses``, first
    ``grads``, parameter ``change``) and the initial weights (host, flat)."""
    from repro.dist.policy import NO_POLICY
    from repro.launch.steps import gnn_loss_fn
    from repro.models.graphcast import graphcast_init
    from repro.train.loop import Trainer, TrainerConfig
    from repro.train.optimizer import adamw

    cfg, opt = program_config(cell.config), cell.traffic["optimizer"]
    params = graphcast_init(prng_key(seed, "weights"), cfg)
    p0 = _flat(params)
    trainer = Trainer(
        gnn_loss_fn("graphcast", cfg, NO_POLICY),
        adamw(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
              weight_decay=opt["weight_decay"]),
        params, TrainerConfig(log_every=10**9))
    del params
    losses = _step(trainer, batches[0])
    grads = {k: v / (1.0 - opt["b1"]) for k, v in _flat(trainer.opt_state["m"]).items()}
    while trainer.step < int(cell.traffic["check_steps"]):
        losses += _step(trainer, batches[trainer.step % len(batches)])
    change = {k: v - p0[k] for k, v in _flat(trainer.params).items()}
    return trainer, {"losses": losses, "grads": grads, "change": change}, p0


def reference_data(cell, graph) -> dict:
    return ref.graph_data(cell.config["model"], graph.mesh_xyz,
                          {n: (getattr(graph, f"{n}_senders"), getattr(graph, f"{n}_receivers"))
                           for n in ref.EDGE_SETS})


def reference(cell, data: dict, exs: list, p0: dict, precision=ref.REFERENCE) -> dict:
    """The reference's ``check_steps`` steps from ``p0``, as `first_steps`
    reports the program's."""
    losses, grads, p = ref.train(p0, data, exs, cell.config["model"], cell.traffic["optimizer"],
                                 int(cell.traffic["check_steps"]), precision)
    return {"losses": losses, "grads": grads, "change": {k: p[k] - p0[k] for k in p0}}


def run(cell, seed: int, seconds: float, tracer, t_start: float, devices) -> dict:
    graph, arrays, setup_graph_s = prepare(cell)
    exs = examples(cell, seed)
    batches = [dict(arrays, grid_inputs=x, grid_target=y) for x, y in exs]
    trainer, got, p0 = first_steps(cell, batches, seed)
    # Set-up's garbage is collected here, and what it leaves is frozen: a
    # collection inside the window then scans only the window's objects.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    steps = failed = 0
    with tracer.window():
        w0 = time.perf_counter()
        while True:
            with tracer.annotate("train.step"):
                loss = _step(trainer, batches[trainer.step % len(batches)])
            steps += 1
            failed += int(not all(math.isfinite(x) for x in loss))
            window_s = time.perf_counter() - w0
            if window_s >= seconds:
                break
    facts = device_facts(devices)
    gc.unfreeze()
    del trainer, batches, arrays
    gc.collect()

    readings = compare.train_readings(got, reference(cell, reference_data(cell, graph), exs, p0))
    return {
        "end_to_end": {"setup_s": setup_s, "step_s": window_s / steps},
        "attempted": steps, "failed": failed,
        "checks": compare.checks(readings, cell.config["limits"]["graphcast_train"]),
        "device": facts,
        "counters": dict(graph.sizes, steps=steps, window_s=window_s,
                         setup_graph_s=setup_graph_s),
    }
