"""PINNED metrics-vs-accounting equalities (ISSUE 8 acceptance).

`repro.obs.instrument` never invents a number — every exported gauge is
fed from a value an existing layer already computes. These tests pin that
contract: the registry snapshot must reproduce, bit-for-bit,

* the halo plan's wire model (`exchange_cost`, `HaloPlan` row counts),
* the plan cache's `plan_cache_stats` counters,
* the blocked adjacency's executed-tile count (``lens.sum()``),
* the serve engine's ``stats()`` (p50/p99 latency, cache hit rate),
* the `DeltaPlanner.apply` report (repair latency, drift gauge).

The slow test drives the 8-device distributed example end to end with
``--trace``/``--metrics``: the snapshot reproduces the plan's wire-byte
accounting, and the exported Chrome trace holds every training step's
spans.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.dataflow import exchange_cost
from repro.core.partition import partition_graph
from repro.core.quant import payload_bits
from repro.dist.delta import DeltaPlanner, GraphDelta
from repro.dist.halo import (
    build_halo_plan,
    get_halo_plan,
    invalidate_halo_plans,
    plan_blocked_adjacency,
    plan_cache_stats,
)
from repro.graph.generators import citation_like
from repro.obs import metrics, trace
from repro.obs.instrument import (
    observe_plan_cache,
    record_blocked,
    record_delta_report,
    record_exchange,
)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _fresh_obs():
    old_reg = metrics.set_default_registry(metrics.MetricsRegistry())
    was_enabled = metrics.enabled()
    metrics.enable()
    old_tracer = trace.set_default_tracer(None)
    yield
    metrics.disable()
    metrics.set_default_registry(old_reg)
    if was_enabled:
        metrics.enable()
    trace.set_default_tracer(old_tracer)


def _mk(n=400, e=2400, k=4, seed=2):
    g = citation_like(n, e, seed=seed)
    part = partition_graph(n, g.edge_index, k, method="bfs", seed=seed, refine=True)
    return g, part


def _gauge(snap, key):
    return snap[key]["value"]


# --------------------------------------------------- halo wire accounting
@pytest.mark.parametrize("payload", [None, "bf16", "int8"])
def test_halo_gauges_equal_exchange_cost(payload):
    g, part = _mk()
    plan = build_halo_plan(part, g.edge_index)
    d = 48
    record_exchange(plan, d, payload)
    snap = metrics.snapshot()
    bits = payload_bits(payload)
    cost = exchange_cost(plan.halo_rows_per_device, d, bits,
                         plan.overlap_fraction())
    assert _gauge(snap, "halo.wire_bytes_per_exchange") == cost.wire_bytes
    assert _gauge(snap, "halo.exposed_bytes_per_exchange") == cost.exposed_bytes
    assert _gauge(snap, "halo.compression_vs_fp32") == cost.compression
    assert _gauge(snap, "halo.payload_bits") == bits
    assert _gauge(snap, "halo.overlap_fraction") == plan.overlap_fraction()
    assert _gauge(snap, "halo.wire_fraction") == plan.wire_fraction()
    assert _gauge(snap, "halo.rows_per_device{tier=total}") == plan.halo_rows_per_device
    assert (_gauge(snap, "halo.rows_per_device{tier=broadcast}")
            == plan.broadcast_rows_per_device)
    bnd = plan.boundary_rows_per_device()
    assert _gauge(snap, "halo.boundary_rows_max_device") == int(bnd.max())
    assert snap["halo.exchanges"]["value"] == 1.0


def test_hierarchical_tier_gauges():
    g, part = _mk(k=8)
    plan = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2)
    record_exchange(plan, 32)
    snap = metrics.snapshot()
    assert (_gauge(snap, "halo.rows_per_device{tier=inter_pod_crossing}")
            == plan.inter_pod_rows_crossing)
    assert (_gauge(snap, "halo.rows_per_device{tier=intra_pod}")
            == plan.intra_pod_rows_per_device)


# ------------------------------------------------------------- plan cache
def test_plan_cache_gauges_mirror_stats():
    g, part = _mk(seed=11)
    w = np.ones(g.n_edges, np.float32)
    get_halo_plan(part, g.edge_index, w)      # miss (or hit if cached before)
    get_halo_plan(part, g.edge_index, w)      # hit — observes stats either way
    snap = metrics.snapshot()
    stats = plan_cache_stats()
    for key in ("hits", "misses", "evictions", "size"):
        assert _gauge(snap, f"plan_cache.{key}") == stats[key], key
    observe_plan_cache()                       # the explicit mirror agrees too
    snap2 = metrics.snapshot()
    stats2 = plan_cache_stats()
    assert _gauge(snap2, "plan_cache.hits") == stats2["hits"]
    invalidate_halo_plans()


# ------------------------------------------------------ executed bsr tiles
def test_blocked_gauges_equal_lens_sum():
    g, part = _mk(n=512, e=3000, k=4, seed=5)
    plan = build_halo_plan(part, g.edge_index)
    tab = plan_blocked_adjacency(plan, block=64)
    record_blocked(tab, scope="plan")
    snap = metrics.snapshot()
    executed = int(tab.lens.sum())
    assert executed == tab.stats()["nnz_blocks"]
    assert _gauge(snap, "bsr.executed_tiles{scope=plan}") == executed
    assert _gauge(snap, "bsr.max_nnzb{scope=plan}") == tab.stats()["max_nnzb"]
    assert (_gauge(snap, "bsr.padded_tile_fraction{scope=plan}")
            == tab.stats()["padded_tile_fraction"])


# ------------------------------------------------------------------ serve
def test_serve_gauges_equal_engine_stats():
    import jax

    from repro.models.gcn import GCNConfig, gcn_init
    from repro.serve.graph import GraphBatcher, hot_query_stream

    g = citation_like(300, 2400, 16, 4, seed=0)
    cfg = GCNConfig(layer_dims=(16, 8, 4))
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=4,
                       cache_capacity=64, seed=0)
    for v in hot_query_stream(g, 24, seed=1):
        eng.submit(int(v))
    eng.run_until_drained()
    s = eng.export_metrics()
    snap = metrics.snapshot()
    assert _gauge(snap, "serve.p50_ms") == s["p50_ms"]
    assert _gauge(snap, "serve.p99_ms") == s["p99_ms"]
    assert _gauge(snap, "serve.cache_hit_rate") == s["cache"]["hit_rate"]
    assert _gauge(snap, "serve.nodes_per_query") == s["nodes_per_query"]
    assert snap["serve.queries"]["value"] == s["queries"] == 24
    assert snap["serve.micro_batches"]["value"] == s["micro_batches"]
    assert snap["serve.latency_ms"]["count"] == 24
    assert snap["serve.queue_wait_ms"]["count"] == 24
    occ = snap["serve.batch_occupancy"]
    assert occ["count"] == s["micro_batches"] and 0.0 < occ["max"] <= 1.0


# ------------------------------------------------------------------ delta
def test_delta_report_gauges_and_drift():
    g, part = _mk(n=256, e=1500, k=4, seed=7)
    w = np.ones(g.n_edges, np.float32)
    pl = DeltaPlanner(part, g.edge_index, w)
    pl.plan()
    rng = np.random.default_rng(0)
    ins = np.stack([rng.integers(0, 256, 12), rng.integers(0, 256, 12)]).astype(np.int64)
    rep = pl.apply(GraphDelta(edge_inserts=ins), measure_drift=True, drift_block=64)
    snap = metrics.snapshot()
    assert snap["delta.applies"]["value"] == 1.0
    assert snap["delta.inserts"]["value"] == rep["inserts"] == 12
    assert _gauge(snap, "delta.dirty_devices") == len(rep["dirty_devices"])
    assert _gauge(snap, "delta.structural") == float(bool(rep["structural"]))
    assert snap["delta.apply_ms"]["count"] == 1
    assert snap["delta.apply_ms"]["sum"] == rep["apply_ms"]
    d = rep["drift"]
    assert d["block"] == 64
    assert _gauge(snap, "delta.drift_ratio") == d["drift_ratio"]
    assert (_gauge(snap, "delta.executed_tiles_current")
            == d["executed_tiles_current"])
    assert (_gauge(snap, "delta.executed_tiles_reordered")
            == d["executed_tiles_reordered"])
    # drift is a ratio of executed-tile counts: >= 0, and both sides > 0
    assert d["executed_tiles_current"] > 0 and d["executed_tiles_reordered"] > 0
    # re-running record_delta_report is additive on counters (apply #2)
    record_delta_report(rep)
    assert metrics.snapshot()["delta.applies"]["value"] == 2.0


# ------------------------------------------- 8-device traced example (slow)
@pytest.mark.slow
def test_traced_example_exports_metrics_subprocess(tmp_path):
    """Drive the distributed example with --trace/--metrics on 8 host
    devices; the metrics snapshot must reproduce the plan's wire-byte
    accounting, and the exported Chrome trace must hold each step's
    ``train.step`` span enclosing its ``train.dispatch`` and ``train.sync``."""
    trace_path = tmp_path / "trace.json"
    metrics_path = tmp_path / "metrics.json"
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "examples/train_distributed_gcn.py", "--steps", "12",
         "--trace", str(trace_path), "--metrics", str(metrics_path)],
        capture_output=True, text=True, timeout=560,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    ev = json.loads(trace_path.read_text())["traceEvents"]
    spans = {name: [e for e in ev if e.get("name") == name]
             for name in ("train.step", "train.dispatch", "train.sync")}
    assert all(len(v) == 12 for v in spans.values())
    for step, d, s in zip(*spans.values()):
        for inner in (d, s):
            assert step["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= step["ts"] + step["dur"]
    snap = json.loads(metrics_path.read_text())
    rows = snap["halo.rows_per_device{tier=total}"]["value"]
    d_feat = 64  # reduced cora feature width (make_dataset("cora", reduced=True))
    assert snap["halo.wire_bytes_per_exchange"]["value"] == rows * d_feat * 4
    assert snap["train.steps"]["value"] == 12.0
    assert snap["train.step_ms"]["count"] == 12
