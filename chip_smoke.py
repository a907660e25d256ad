#!/usr/bin/env python3
"""Bring-up check: the main path of this repository on TPU chips.

    python chip_smoke.py              # one chip: train, bsr kernels, serve
    python chip_smoke.py --chips 4    # one v5e:2x2 host: the halo path only

One process, JAX imported once; any failed phase exits non-zero. The paper's
own model, coin_gcn, runs at its published widths (Table I), with random
weights from a fixed seed:

one chip
  train  `repro.launch.train --shape nell`: 65,755 nodes, 266,144 edges (plus
         self-loops), layers 5414→16→210, segment backend; finite losses.
  bsr    the fused ragged-BSR Pallas kernels on pubmed in the BFS locality
         order: the compiled forward must hold a native kernel
         (``tpu_custom_call``), its logits must match the segment backend on
         the same params, and a few `Trainer` steps must give finite losses.
  serve  `repro.launch.serve --shape nell --queries 64`: every query is
         answered, and the cached engine's logits match a cache-off engine
         on the same queries.
four chips
  halo   coin_gcn on pubmed over the halo exchange, on a flat (4,) "model"
         mesh and on a (2, 2) (pod, model) mesh, each with the segment and the
         bsr backends (split blocked tables, Pallas inside shard_map). Every
         device must hold its own block of the plan; the forward logits and
         the first training loss must match the one-device global forward on
         the same params.

Logit comparisons run quantization off and matmuls at ``highest``
precision, so backends and layouts that differ only in summation order must
agree to fp32 rounding: the published config fake-quantizes activations
per tensor, and a rounding flip there moves a value by a whole 4-bit step.
Training steps run the published config (4-bit QAT) unless they are
compared against the global loss.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
TOL = 1e-4          # max |diff| over max(1, max |ref|), fp32 comparisons
STEPS = 3           # Trainer steps of the bsr and halo phases


def say(msg: str) -> None:
    print(msg, flush=True)


def check_close(what: str, got, want, tol: float = TOL) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} vs reference {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    err = float(np.abs(got - want).max())
    bound = tol * max(1.0, float(np.abs(want).max()))
    say(f"  {what}: max |diff| {err:.3e} (bound {bound:.3e})")
    if err > bound:
        raise AssertionError(f"{what}: max |diff| {err:.3e} > {bound:.3e}")


def check_losses(what: str, losses, n: int) -> None:
    if len(losses) != n or not np.isfinite(losses).all():
        raise AssertionError(f"{what}: expected {n} finite losses, got {losses}")
    say(f"  {what}: losses {[round(float(x), 4) for x in losses]}")


# ----------------------------------------------------------------- host data
def pubmed_graph():
    """pubmed at full size, with self-loops and Kipf–Welling weights, in the
    BFS locality order the blocked layout is built in (host numpy)."""
    from repro.graph.generators import make_dataset
    from repro.graph.structure import (
        GraphData,
        locality_block_order,
        permute_edge_index,
        relocate_rows,
    )

    _, g = make_dataset("pubmed")
    g = g.with_self_loops()
    perm = locality_block_order(g.n_nodes, g.edge_index)
    return GraphData(
        n_nodes=g.n_nodes,
        edge_index=permute_edge_index(perm, g.edge_index),
        edge_weight=g.sym_normalized_weights(),
        features=relocate_rows(perm, g.features).astype(np.float32),
        labels=relocate_rows(perm, g.labels).astype(np.int32),
    )


def global_batch(g, blocked: bool) -> dict:
    """One-device batch of `repro.launch.steps.gnn_loss_fn` (host numpy);
    ``blocked`` adds the ragged blocked adjacency of the bsr backend."""
    batch = {
        "feats": g.features,
        "senders": g.edge_index[0],
        "receivers": g.edge_index[1],
        "edge_weight": g.edge_weight,
        "labels": g.labels,
        "label_mask": np.ones(g.n_nodes, np.float32),
    }
    if blocked:
        from repro.graph.structure import blocked_adjacency

        ba = blocked_adjacency(g.n_nodes, g.edge_index, g.edge_weight)
        say(f"  blocked adjacency: R={ba.n_block_rows} T={ba.max_nnzb} "
            f"nnz tiles={ba.nnz_blocks} ({ba.block_vals.nbytes / 1e9:.2f} GB)")
        batch.update(bsr_vals=ba.block_vals, bsr_cols=ba.block_cols,
                     bsr_lens=ba.row_nnzb)
    return batch


def halo_batch(g, plan, backend: str) -> dict:
    """The plan's blocked layout of ``g`` (host numpy, one leading slice per
    device) in the keys of `repro.launch.steps.halo_loss_fn`."""
    from repro.dist.halo import node_mask, plan_split_blocked_adjacency, relocate_node_array

    send = ({"send_loc": plan.send_loc, "send_rem": plan.send_rem}
            if plan.is_hierarchical else {"send_idx": plan.send_idx})
    batch = {
        "feats": relocate_node_array(plan, g.features),
        "labels": relocate_node_array(plan, g.labels),
        "label_mask": node_mask(plan),
        "senders": plan.senders_l, "receivers": plan.receivers_l, "edge_w": plan.edge_w,
        **send,
    }
    if backend == "bsr":
        for prefix, t in zip(("bsr_", "bsr_b"), plan_split_blocked_adjacency(plan)):
            batch.update({prefix + "vals": t.vals, prefix + "cols": t.cols,
                          prefix + "lens": t.lens})
    ints = ("labels", "senders", "receivers", "send_idx", "send_loc", "send_rem",
            "bsr_cols", "bsr_lens", "bsr_bcols", "bsr_blens")
    return {k: np.asarray(v, np.int32 if k in ints else np.float32)
            for k, v in batch.items()}


# -------------------------------------------------------------------- phases
def phase_train() -> None:
    from repro.launch import train

    say("train: repro.launch.train --arch coin_gcn --shape nell --steps 5")
    t0 = time.perf_counter()
    losses = train.main(["--arch", "coin_gcn", "--shape", "nell", "--steps", "5"])
    check_losses("nell segment", losses, 5)
    say(f"  train phase {time.perf_counter() - t0:.1f} s (set-up and compile included)")


def phase_bsr() -> None:
    import jax

    from repro.configs import get_arch
    from repro.core.quant import QuantConfig
    from repro.dist.policy import NO_POLICY
    from repro.launch.steps import gnn_loss_fn
    from repro.models.gcn import gcn_forward, gcn_init
    from repro.train.loop import Trainer
    from repro.train.optimizer import adamw

    say("bsr: fused ragged-BSR kernels, coin_gcn at pubmed width")
    t0 = time.perf_counter()
    spec = get_arch("coin_gcn")
    cfg = spec.make_config(spec.shapes["pubmed"])
    g = pubmed_graph()
    batch = jax.device_put(global_batch(g, blocked=True))
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    say(f"  set-up {time.perf_counter() - t0:.1f} s")
    fp32 = dataclasses.replace(cfg, quant=QuantConfig(enabled=False))

    def forward(c):
        def fwd(p, b):
            adj = (b["bsr_vals"], b["bsr_cols"], b["bsr_lens"]) if c.backend == "bsr" else None
            return gcn_forward(p, b["feats"], b["senders"], b["receivers"],
                               b["edge_weight"], c, adjacency=adj)
        return jax.jit(fwd)

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        compiled = forward(dataclasses.replace(fp32, backend="bsr")).lower(params, batch).compile()
        say(f"  bsr forward compile {time.perf_counter() - t0:.2f} s")
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError("bsr forward: no tpu_custom_call in the compiled HLO")
        got = compiled(params, batch)
        want = forward(dataclasses.replace(fp32, backend="segment"))(params, batch)
        check_close("bsr vs segment logits", got, want)

    tr = Trainer(gnn_loss_fn("coin_gcn", dataclasses.replace(cfg, backend="bsr"), NO_POLICY),
                 adamw(1e-3), params)
    losses = tr.fit(itertools.repeat(batch), max_steps=STEPS)
    check_losses("bsr Trainer (4-bit QAT)", losses, STEPS)
    say(f"  step seconds {[round(t, 4) for t in tr.step_seconds]} (first includes compile)")


def phase_serve() -> None:
    from repro.launch import serve
    from repro.serve.graph import GraphBatcher

    say("serve: repro.launch.serve --arch coin_gcn --shape nell --queries 64")
    t0 = time.perf_counter()
    eng = serve.main(["--arch", "coin_gcn", "--shape", "nell", "--queries", "64"])
    done = eng.finished
    if len(done) != 64 or any(q.logits is None for q in done):
        raise AssertionError(f"serve: {len(done)} of 64 queries answered")
    # Cache-off reference on the same graph and sampler seed (serve.main's 0).
    ref = GraphBatcher(eng.params, eng.graph, eng.cfg, batch_seeds=eng.batch_seeds,
                       fanout=eng.sampler.fanout, cache_capacity=0, seed=0)
    for v in sorted({q.node for q in done}):
        ref.submit(v)
    ref.run_until_drained()
    want = {q.node: q.logits for q in ref.finished}
    check_close("cache-on vs cache-off logits",
                np.stack([q.logits for q in done]),
                np.stack([want[q.node] for q in done]))
    say(f"  cache hits {eng.cache.hits}, misses {eng.cache.misses}; "
        f"serve phase {time.perf_counter() - t0:.1f} s")


def phase_halo() -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_arch
    from repro.core.partition import partition_graph
    from repro.core.quant import QuantConfig
    from repro.dist.halo import get_halo_plan, restore_node_array
    from repro.dist.policy import ShardingPolicy
    from repro.launch.mesh import halo_axes, make_halo_mesh, make_mesh
    from repro.launch.steps import gcn_device_logits, halo_apply, halo_loss_fn
    from repro.models.gcn import gcn_forward, gcn_init, gcn_loss
    from repro.train.loop import Trainer
    from repro.train.optimizer import adamw

    say("halo: coin_gcn at pubmed width over 4 chips")
    spec = get_arch("coin_gcn")
    cfg = dataclasses.replace(spec.make_config(spec.shapes["pubmed"]),
                              quant=QuantConfig(enabled=False))
    g = pubmed_graph()
    # Host copies: each layout's Trainer donates its own device copy.
    params = jax.tree_util.tree_map(np.asarray, gcn_init(jax.random.PRNGKey(0), cfg))
    ref_batch = jax.device_put(global_batch(g, blocked=False), jax.devices()[0])
    with jax.default_matmul_precision("highest"):
        want_logits = np.asarray(jax.jit(
            lambda p, b: gcn_forward(p, b["feats"], b["senders"], b["receivers"],
                                     b["edge_weight"], cfg))(params, ref_batch))
        want_loss = float(jax.jit(
            lambda p, b: gcn_loss(p, b["feats"], b["senders"], b["receivers"],
                                  b["edge_weight"], b["labels"], b["label_mask"], cfg)
        )(params, ref_batch))
    part = partition_graph(g.n_nodes, g.edge_index, 4, method="bfs", seed=0, refine=True)

    for pods in (1, 2):
        mesh = make_mesh((4,), ("model",)) if pods == 1 else make_halo_mesh(2, 2)
        axes = halo_axes(mesh)
        plan = get_halo_plan(part, g.edge_index, g.edge_weight,
                             **({"pods": pods} if pods > 1 else {}))
        policy = ShardingPolicy(comm="halo", halo_axes=axes if pods > 1 else None)
        spec_axes = axes if pods > 1 else "model"
        for backend in ("segment", "bsr"):
            name = f"mesh {mesh.devices.shape} {backend}"
            c = dataclasses.replace(cfg, backend=backend)
            host = halo_batch(g, plan, backend)
            batch = jax.device_put(host, NamedSharding(mesh, P(spec_axes)))
            check_placement(mesh, batch, host, plan)
            rep = jax.device_put(params, NamedSharding(mesh, P()))
            with jax.default_matmul_precision("highest"):
                fwd = jax.jit(halo_apply(gcn_device_logits(c), mesh, policy))
                compiled = fwd.lower(rep, batch).compile()
                if backend == "bsr" and "tpu_custom_call" not in compiled.as_text():
                    raise AssertionError(f"{name}: no tpu_custom_call in the compiled HLO")
                got = restore_node_array(plan, np.asarray(compiled(rep, batch)))
                check_close(f"{name} logits vs one device", got, want_logits)
                tr = Trainer(halo_loss_fn("coin_gcn", c, mesh, policy), adamw(1e-3), rep)
                losses = tr.fit(itertools.repeat(batch), max_steps=STEPS)
            check_losses(f"{name} Trainer", losses, STEPS)
            check_close(f"{name} first loss vs one device", losses[0], want_loss)
            say(f"  step seconds {[round(t, 4) for t in tr.step_seconds]} "
                "(first includes compile)")


def check_placement(mesh, batch: dict, host: dict, plan) -> None:
    """Each device of the mesh holds one non-empty block of the plan, and
    block g sits on the mesh's g-th device — checked on the arrays, not
    assumed from the sharding spec."""
    import jax

    devs = list(mesh.devices.flat)
    if sorted(d.id for d in devs) != sorted(d.id for d in jax.devices()):
        raise AssertionError(f"mesh {[d.id for d in devs]} does not cover every device")
    if (np.asarray(plan.part_sizes) == 0).any():
        raise AssertionError(f"empty block in the plan: {plan.part_sizes}")
    for key, arr in batch.items():
        on = {s.device: s for s in arr.addressable_shards}
        for g, d in enumerate(devs):
            s = on.get(d)
            if s is None or s.index[0] != slice(g, g + 1):
                raise AssertionError(f"{key}: device {d.id} does not hold block {g}")
            if key == "feats" and not np.array_equal(np.asarray(s.data)[0], host[key][g]):
                raise AssertionError(f"{key}: device {d.id} holds the wrong rows")
    say(f"  placement: block g on mesh device g, ids {[d.id for d in devs]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip halo path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    say(f"device: {dev.device_kind} x{len(devices)} (jax {jax.__version__})")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    t0 = time.perf_counter()
    phases = (phase_halo,) if args.chips == 4 else (phase_train, phase_bsr, phase_serve)
    for phase in phases:
        phase()
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
