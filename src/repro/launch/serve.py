"""Serving driver: batched decode for LM archs, batched scoring for DeepFM,
and online GCN node-query serving with the hot-neighbor cache (DESIGN.md §9).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --tokens 32
    PYTHONPATH=src python -m repro.launch.serve --arch deepfm --requests 4
    PYTHONPATH=src python -m repro.launch.serve --arch coin-gcn --queries 64
    PYTHONPATH=src python -m repro.launch.serve --arch coin_gcn --shape nell --queries 64

``--shape`` serves coin_gcn's published config on that Table-I dataset at full
size instead of the reduced config on a 2000-node graph.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.obsflags import add_obs_args, obs_session


def serve_lm(spec, gen_tokens: int, batch: int = 4) -> None:
    from repro.models.transformer_lm import lm_decode_step, lm_init, lm_init_cache

    cfg = spec.make_reduced()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    max_len = gen_tokens + 8
    cache = lm_init_cache(cfg, batch, max_len)
    decode = jax.jit(lm_decode_step, static_argnames=("cfg",))
    tok = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, cfg.vocab)
    t0 = time.perf_counter()
    for t in range(gen_tokens):
        logits, cache = decode(params, cache, tok, jnp.asarray(t, jnp.int32), cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    dt = time.perf_counter() - t0
    print(f"{spec.arch_id}: {batch}×{gen_tokens} tokens in {dt*1e3:.1f} ms "
          f"({batch*gen_tokens/dt:.0f} tok/s)")


def serve_recsys(spec, requests: int, batch: int = 512) -> None:
    from repro.models.deepfm import deepfm_forward, deepfm_init

    cfg = spec.make_reduced()
    params = deepfm_init(jax.random.PRNGKey(0), cfg)
    fwd = jax.jit(lambda p, ids: deepfm_forward(p, ids, cfg))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.rows_per_field, (batch, cfg.n_fields)), jnp.int32)
    fwd(params, ids).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(requests):
        fwd(params, ids).block_until_ready()
    dt = (time.perf_counter() - t0) / requests
    print(f"deepfm: batch={batch} p50≈{dt*1e3:.2f} ms ({batch/dt:.0f} examples/s)")


def build_graph_engine(
    spec,
    batch_seeds: int = 8,
    fanout: int = 4,
    cache_capacity: int = 256,
    n_parts: int = 0,
    seed: int = 0,
    n_nodes: int = 2000,
    n_edges: int = 12000,
    shape: str | None = None,
):
    """A serving engine for a GNN arch: the reduced config on a small
    citation-like graph, or — with ``shape`` — coin_gcn's published config
    on that Table-I dataset at full size.

    Returns (engine, graph). Shared by the CLI, the example, and the serve
    benchmark so they exercise one code path.
    """
    from repro.core.partition import partition_graph
    from repro.graph.generators import citation_like, make_dataset
    from repro.serve.graph import GraphBatcher

    if shape is not None and spec.arch_id != "coin_gcn":
        raise SystemExit(f"--shape serves coin_gcn on a Table-I dataset; "
                         f"{spec.arch_id} has no dataset for {shape!r}")
    cfg = spec.make_reduced() if shape is None else spec.make_config(spec.shapes[shape])
    part = None
    if spec.arch_id == "coin_gcn":
        from repro.models.gcn import gcn_init

        d_in, n_out = cfg.layer_dims[0], cfg.layer_dims[-1]
        if shape is None:
            graph = citation_like(n_nodes, n_edges, d_in, n_out, seed=seed)
        else:
            _, graph = make_dataset(shape, seed=seed)
        params = gcn_init(jax.random.PRNGKey(seed), cfg)
        model = "gcn"
    elif spec.arch_id == "pna":
        from repro.models.pna import pna_init

        graph = citation_like(n_nodes, n_edges, cfg.d_in, 4, seed=seed)
        params = pna_init(jax.random.PRNGKey(seed), cfg)
        model = "pna"
    elif spec.arch_id == "egnn":
        from repro.models.egnn import egnn_init

        graph = citation_like(n_nodes, n_edges, cfg.d_in, 4, seed=seed, with_positions=True)
        params = egnn_init(jax.random.PRNGKey(seed), cfg)
        model = "egnn"
    else:
        raise SystemExit(f"{spec.arch_id}: graph serving supports coin_gcn/pna/egnn")
    if n_parts:
        part = partition_graph(graph.n_nodes, graph.edge_index, n_parts, method="bfs",
                               seed=seed, refine=True)
    engine = GraphBatcher(
        params, graph, cfg,
        model=model, batch_seeds=batch_seeds, fanout=fanout,
        # Activation injection (the cache's truncation hook) exists only in
        # the GCN serve forward; other archs serve cache-off.
        cache_capacity=cache_capacity if model == "gcn" else 0,
        partition=part, seed=seed,
    )
    return engine, graph


def serve_graph(
    spec,
    n_queries: int,
    batch_seeds: int = 8,
    fanout: int = 4,
    cache_capacity: int = 256,
    n_parts: int = 4,
    seed: int = 0,
    relocalize_threshold: float = 0.0,
    shape: str | None = None,
):
    """Serve ``n_queries`` node-classification queries (degree-weighted, so
    hub neighborhoods are hot — the COIN access pattern) and report latency
    plus hot-neighbor-cache accounting. Returns the engine, whose
    ``finished`` queries hold the served logits.

    With ``relocalize_threshold`` > 0 a churn burst is injected halfway
    through the stream: each delta goes to both the engine
    (`apply_graph_delta`, scoped cache invalidation) and a mirrored
    `DeltaPlanner` whose `RelocalizePolicy` watches drift; when it fires,
    the engine adopts the re-localized partition (docs/communication.md §8).
    """
    from repro.serve.graph import hot_query_stream

    engine, graph = build_graph_engine(
        spec, batch_seeds=batch_seeds, fanout=fanout,
        cache_capacity=cache_capacity, n_parts=n_parts, seed=seed, shape=shape,
    )
    planner = None
    if relocalize_threshold > 0 and engine.partition is not None:
        from repro.dist.delta import DeltaPlanner, RelocalizePolicy

        planner = DeltaPlanner(
            engine.partition, graph.edge_index, graph_key="launch-serve",
            relocalize_policy=RelocalizePolicy(
                threshold=relocalize_threshold, patience=2, cooldown=3))
    nodes = hot_query_stream(graph, n_queries, seed=seed + 1)
    t0 = time.perf_counter()
    half = len(nodes) // 2 if planner is not None else len(nodes)
    for v in nodes[:half]:
        engine.submit(int(v))
    engine.run_until_drained()
    if planner is not None:
        fired = _serve_churn_burst(engine, planner, graph, seed)
        for v in nodes[half:]:
            engine.submit(int(v))
        engine.run_until_drained()
        drift = planner.locality_drift()["drift_ratio"]
        print(f"  maintenance: {fired} relocalization(s) over churn burst, "
              f"residual drift {drift:.3f}")
    dt = time.perf_counter() - t0
    s = engine.export_metrics()       # == stats(), mirrored into the registry
    print(
        f"{spec.arch_id}: {s['queries']} queries in {s['micro_batches']} micro-batches "
        f"({s['traces']} trace) in {dt*1e3:.1f} ms ({s['queries']/dt:.0f} q/s)"
    )
    print(
        f"  latency p50={s['p50_ms']:.2f} ms p99={s['p99_ms']:.2f} ms | "
        f"sampled {s['nodes_per_query']:.1f} nodes/q {s['edges_per_query']:.1f} edges/q"
        + (f" | foreign rows {s['foreign_rows']}" if n_parts else "")
    )
    if "cache" in s:
        c = s["cache"]
        print(
            f"  hot-neighbor cache: hit-rate {c['hit_rate']:.1%} "
            f"({c['hits']} hits / {c['misses']} misses), resident {c['resident']}/"
            f"{c['capacity']}, evictions {c['evictions']}, "
            f"rows saved {c['rows_saved']}, bytes saved {c['bytes_saved']/1e3:.1f} kB"
        )
    return engine


def _serve_churn_burst(engine, planner, graph, seed: int, rounds: int = 8) -> int:
    """Apply ``rounds`` clustered churn deltas to engine AND planner; adopt
    the re-localized partition whenever the policy fires. Returns #fires."""
    from repro.dist.delta import GraphDelta

    churn = np.random.default_rng(seed + 2)
    fired = 0
    for _ in range(rounds):
        ei = planner.edge_index()
        m = max(ei.shape[1] // 50, 2)
        drop = churn.choice(ei.shape[1], m, replace=False)
        mem = churn.choice(graph.n_nodes, 24, replace=False)
        s = mem[churn.integers(0, mem.size, m)]
        d = mem[churn.integers(0, mem.size, m)]
        bad = s == d
        d[bad] = mem[(np.searchsorted(np.sort(mem), d[bad]) + 1) % mem.size]
        delta = GraphDelta(edge_inserts=np.stack([s, d]), edge_deletes=ei[:, drop])
        engine.apply_graph_delta(delta)
        rep = planner.apply(delta)
        if rep["relocalized"] is not None:
            fired += 1
            engine.adopt_partition(planner.part)
    return fired


def main(argv=None):
    """Serve the arch; for a GNN returns the `GraphBatcher` that served."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {', '.join(ALL_ARCHS)} (hyphen/underscore both fine)")
    ap.add_argument("--shape", default=None,
                    help="registry shape to serve at full size "
                         "(coin_gcn: a Table-I dataset, e.g. nell)")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--queries", type=int, default=64, help="graph node queries to serve")
    ap.add_argument("--batch-seeds", type=int, default=8)
    ap.add_argument("--fanout", type=int, default=4)
    ap.add_argument("--cache-capacity", type=int, default=256)
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--parts", type=int, default=4, help="partition-aligned packing parts")
    ap.add_argument("--relocalize-threshold", type=float, default=0.0,
                    help="drift ratio beyond which a mid-stream churn burst "
                         "triggers online re-localization (0 = off; gnn only)")
    add_obs_args(ap)
    args = ap.parse_args(argv)
    spec = get_arch(args.arch)
    if args.shape is not None:
        if spec.family != "gnn":
            ap.error(f"--shape is for GNN archs; {args.arch} is {spec.family}")
        if args.shape not in spec.shapes:
            ap.error(f"--shape {args.shape!r}: {args.arch} has {sorted(spec.shapes)}")
    use_compile_cache()
    with obs_session(args):
        if spec.family == "lm":
            serve_lm(spec, args.tokens)
        elif spec.family == "recsys":
            serve_recsys(spec, args.requests)
        elif spec.family == "gnn":
            return serve_graph(
                spec, args.queries,
                batch_seeds=args.batch_seeds, fanout=args.fanout,
                cache_capacity=0 if args.no_cache else args.cache_capacity,
                n_parts=args.parts,
                relocalize_threshold=args.relocalize_threshold,
                shape=args.shape,
            )
        else:
            raise SystemExit(
                f"{args.arch} is a training architecture; use repro.launch.train")


if __name__ == "__main__":
    main()
