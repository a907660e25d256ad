"""Communication-aware autotuner (docs/autotune.md): quotient-graph pod
mapper invariants, refine_partition determinism/monotonicity, plan-cache
pod_map keying, the pinned predicted==measured calibration contract on the
2×4 worked example, the pinned benchmark-graph crossing win, and the
8-device autotuned-vs-default logits equivalence (slow).
"""
import dataclasses
import os
import subprocess
import sys
import types

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.autotune import (
    BoundaryIndex,
    CandidateConfig,
    _crossing_objective,
    autotune_config,
    comm_stats_from_plan,
    map_parts_to_pods,
    predict_config_cost,
    refine_pod_map,
)
from repro.core.partition import partition_graph, quotient_graph, refine_partition
from repro.dist.halo import build_halo_plan
from repro.graph.generators import citation_like

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _graph(n, e, seed):
    g = citation_like(n, e, seed=seed)
    return g, g.edge_index


# ------------------------------------------------------- quotient graph (S4)
@settings(max_examples=15, deadline=None)
@given(n=st.integers(60, 300), k=st.sampled_from([4, 8]), seed=st.integers(0, 5))
def test_quotient_weights_sum_to_dedup_boundary_rows(n, k, seed):
    """Σ quotient weights == total deduplicated boundary (node, dest-part)
    pairs — the unit the halo export tiers pad — and the weight matrix is
    exactly the BoundaryIndex row-traffic matrix."""
    g, ei = _graph(n, 5 * n, seed)
    part = partition_graph(n, ei, k, method="bfs", seed=0)
    q_ei, q_w = quotient_graph(part, ei)
    index = BoundaryIndex(part, ei)
    assert int(q_w.sum()) == index.pair_node.size
    dense = np.zeros((k, k), np.int64)
    dense[q_ei[0], q_ei[1]] = q_w
    np.testing.assert_array_equal(dense, index.row_traffic)
    assert not np.any(q_ei[0] == q_ei[1])          # self-loops dropped
    assert np.all(q_w > 0)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(80, 300), pods=st.sampled_from([2, 4]), seed=st.integers(0, 4))
def test_pod_map_balanced_and_deterministic(n, pods, seed):
    """map_parts_to_pods hosts exactly k/pods parts per pod and is a pure
    function of its inputs (same call twice → identical array)."""
    k = 8
    g, ei = _graph(n, 5 * n, seed)
    part = partition_graph(n, ei, k, method="bfs", seed=0)
    pm = map_parts_to_pods(part, ei, pods)
    np.testing.assert_array_equal(np.bincount(pm, minlength=pods), k // pods)
    np.testing.assert_array_equal(pm, map_parts_to_pods(part, ei, pods))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(80, 250), seed=st.integers(0, 4), perm_seed=st.integers(0, 100))
def test_pod_map_edge_order_invariance(n, seed, perm_seed):
    """Permuting the edge list changes nothing: the quotient/index dedup via
    np.unique is order-free, so the mapper's output is identical."""
    g, ei = _graph(n, 5 * n, seed)
    part = partition_graph(n, ei, 8, method="bfs", seed=0)
    perm = np.random.default_rng(perm_seed).permutation(ei.shape[1])
    pm_a = map_parts_to_pods(part, ei, 2)
    pm_b = map_parts_to_pods(part, ei[:, perm], 2)
    np.testing.assert_array_equal(pm_a, pm_b)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(80, 250), seed=st.integers(0, 4), map_seed=st.integers(0, 50))
def test_refine_pod_map_monotone_and_balanced(n, seed, map_seed):
    """FM swap passes never increase the crossing objective and preserve the
    exact per-pod part count of ANY balanced starting map."""
    k, pods = 8, 2
    g, ei = _graph(n, 5 * n, seed)
    part = partition_graph(n, ei, k, method="bfs", seed=0)
    index = BoundaryIndex(part, ei)
    start = np.repeat(np.arange(pods), k // pods)
    np.random.default_rng(map_seed).shuffle(start)
    refined = refine_pod_map(start, pods, index)
    assert _crossing_objective(refined, pods, index) <= _crossing_objective(start, pods, index)
    np.testing.assert_array_equal(np.bincount(refined, minlength=pods), k // pods)
    # Idempotent at a local optimum: re-refining moves nothing.
    np.testing.assert_array_equal(refine_pod_map(refined, pods, index), refined)


# ----------------------------------------------------- refine_partition (S2)
@settings(max_examples=10, deadline=None)
@given(n=st.integers(60, 250), k=st.sampled_from([4, 8]), seed=st.integers(0, 4),
       perm_seed=st.integers(0, 100))
def test_refine_partition_edge_order_invariant(n, k, seed, perm_seed):
    g, ei = _graph(n, 4 * n, seed)
    base = partition_graph(n, ei, k, method="block")
    perm = np.random.default_rng(perm_seed).permutation(ei.shape[1])
    a = refine_partition(base.assignment, k, ei[0], ei[1], passes=3)
    b = refine_partition(base.assignment, k, ei[0][perm], ei[1][perm], passes=3)
    np.testing.assert_array_equal(a, b)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(60, 250), k=st.sampled_from([4, 8]), seed=st.integers(0, 4),
       passes=st.integers(1, 5))
def test_refine_partition_cut_monotone_and_balance_capped(n, k, seed, passes):
    """The cut never exceeds the input's cut (a worsening pass is reverted)
    and no part ever grows past the balance cap."""
    g, ei = _graph(n, 4 * n, seed)
    base = partition_graph(n, ei, k, method="block")
    src, dst = ei[0], ei[1]
    cut0 = int((base.assignment[src] != base.assignment[dst]).sum())
    refined = refine_partition(base.assignment, k, src, dst, passes=passes)
    cut1 = int((refined[src] != refined[dst]).sum())
    assert cut1 <= cut0
    cap = int(np.ceil(n / k) * 1.05) + 1
    sizes0 = np.bincount(base.assignment, minlength=k)
    sizes1 = np.bincount(refined, minlength=k)
    assert np.all(sizes1 <= np.maximum(sizes0, cap))


# -------------------------------------------- BoundaryIndex calibration
@settings(max_examples=8, deadline=None)
@given(n=st.integers(100, 300), seed=st.integers(0, 4), tuned=st.booleans())
def test_boundary_index_matches_built_plan(n, seed, tuned):
    """index.comm_stats(pods, pod_map) == comm_stats_from_plan(built plan)
    for flat, default-map hierarchical, and autotuned-map hierarchical —
    the analytic model IS the plan geometry."""
    k, pods = 8, 2
    g, ei = _graph(n, 6 * n, seed)
    part = partition_graph(n, ei, k, method="bfs", seed=0, refine=True)
    index = BoundaryIndex(part, ei)
    flat = build_halo_plan(part, ei)
    assert index.comm_stats() == comm_stats_from_plan(flat)
    pm = map_parts_to_pods(part, ei, pods, index=index) if tuned else None
    hier = build_halo_plan(part, ei, axes=("pod", "model"), pods=pods, pod_map=pm)
    assert index.comm_stats(pods, pm) == comm_stats_from_plan(hier)


# ------------------------------------- pinned 2×4 worked example (S3)
def _worked_example():
    g = citation_like(2000, 12000, seed=1)
    part = partition_graph(2000, g.edge_index, 8, method="bfs", seed=0, refine=True)
    return g, part


def test_dryrun_predicted_matches_measured_worked_example():
    """The autotuner's prediction for a cell's own config agrees EXACTLY
    with the cell's measured ``exchange_accounting`` on the
    docs/communication.md 2×4 worked example — the shipped calibration
    contract, pinned to the documented numbers."""
    from repro.launch.steps import exchange_accounting

    g, part = _worked_example()
    plan = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2)
    # The documented geometry (docs/communication.md §5–§6).
    assert (plan.n_local, plan.s_max, plan.s_loc, plan.s_rem) == (263, 40, 31, 25)
    assert plan.halo_rows_per_device == 374          # 2·25 + 4·(31 + 2·25)
    assert plan.inter_pod_rows_crossing == 25
    assert plan.flat_inter_pod_rows_crossing == 160  # (2−1)·4·40
    assert plan.overlap_fraction() == 0.6869166666666666

    def predicted(payload=None, overlap=False):
        cfg = CandidateConfig(
            pods=plan.n_pods, backend="segment", payload=payload, overlap=overlap
        )
        return predict_config_cost(
            cfg, comm_stats_from_plan(plan), d_feat=64, n_nodes=plan.n_nodes,
            n_edges=int((plan.edge_w > 0).sum()),
        )

    shape = types.SimpleNamespace(d_feat=64)
    for payload, overlap in ((None, False), ("int8", True)):
        cell = types.SimpleNamespace(
            comm="halo", halo_plan=plan, halo_payload=payload, halo_overlap=overlap
        )
        acc = exchange_accounting(cell, shape)
        pred = predicted(payload, overlap)
        for f in (
            "halo_rows_per_device", "broadcast_rows_per_device", "wire_fraction",
            "halo_bytes_per_exchange", "payload", "payload_bits",
            "payload_compression", "overlap", "overlap_fraction",
            "halo_wire_bytes_per_exchange", "halo_exposed_bytes_per_exchange",
            "pods", "intra_pod_rows_per_device", "inter_pod_rows_per_device",
            "inter_pod_rows_crossing", "flat_inter_pod_rows_crossing",
            "inter_pod_bytes_crossing", "flat_inter_pod_bytes_crossing",
        ):
            assert pred[f] == acc[f], (payload, overlap, f, pred[f], acc[f])
    # Pinned fp32 bytes: 374 rows × 64 feats × 4 B.
    cell = types.SimpleNamespace(comm="halo", halo_plan=plan)
    acc = exchange_accounting(cell, shape)
    assert acc["halo_wire_bytes_per_exchange"] == 374 * 64 * 4
    assert predicted()["halo_wire_bytes_per_exchange"] == 374 * 64 * 4
    assert predicted()["halo_exposed_bytes_per_exchange"] == 374 * 64 * 4


def test_predict_config_cost_rejects_pod_mismatch():
    g, part = _worked_example()
    stats = BoundaryIndex(part, g.edge_index).comm_stats(2)
    with pytest.raises(ValueError):
        predict_config_cost(CandidateConfig(pods=1), stats, d_feat=64)


def test_autotune_config_improves_predicted_objective():
    """Coordinate descent on the worked example: the chosen config's
    predicted objective is no worse than the seed defaults', the history is
    non-trivial, and the chosen pod_map is balanced."""
    g, part = _worked_example()
    result = autotune_config(part, g.edge_index, pods=2, d_feat=64,
                             layer_dims=(64, 32, 7))
    assert result.predicted["objective_s"] <= result.baseline["objective_s"]
    assert result.predicted_improvement >= 1.0
    assert result.history[0][0] == "seed defaults" and len(result.history) >= 2
    assert result.config.pods == 2
    if result.config.pod_map is not None:
        pm = np.asarray(result.config.pod_map)
        np.testing.assert_array_equal(np.bincount(pm, minlength=2), 4)


# -------------------------------- pinned benchmark-graph crossing win (S4)
def test_pod_mapper_beats_contiguous_on_benchmark_graph():
    """The pinned BENCH_autotune case (16384 n / 65536 e power-law, shuffled
    node ids, k=32, pods=2): the quotient mapper's deduplicated inter-pod
    crossing rows beat the naive contiguous map by ≥ 1.3× (exact pinned
    values — everything upstream is seeded)."""
    g = citation_like(16384, 65536, n_labels=128, homophily=0.9, seed=1)
    ei = np.random.default_rng(7).permutation(16384)[g.edge_index]
    part = partition_graph(16384, ei, 32, method="bfs", seed=0, refine=True)
    index = BoundaryIndex(part, ei)
    _, s_rem_default = index.tier_sizes(2, None)
    pm = map_parts_to_pods(part, ei, 2, index=index)
    _, s_rem_tuned = index.tier_sizes(2, pm)
    assert (s_rem_default, s_rem_tuned) == (30, 21)
    assert s_rem_default / s_rem_tuned >= 1.3


# --------------------------------------------------- plan cache keying (S1)
def test_plan_cache_default_and_pod_map_coexist():
    """Default-map and autotuned-map hierarchical plans of the SAME graph
    cache under distinct keys (pod_map fingerprint in the axes component),
    stay identity-stable, and ONE graph-scoped invalidation evicts every
    flavor (mirrors test_plan_cache_flat_and_hier_coexist)."""
    from repro.dist import halo

    halo.invalidate_halo_plans()
    g = citation_like(300, 1800, seed=2)
    part = partition_graph(300, g.edge_index, 8, method="bfs", seed=0)
    pm = map_parts_to_pods(part, g.edge_index, 2)
    flat = halo.get_halo_plan(part, g.edge_index)
    default = halo.get_halo_plan(part, g.edge_index, pods=2)
    tuned = halo.get_halo_plan(part, g.edge_index, pods=2, pod_map=pm)
    assert default is not tuned and tuned.is_hierarchical
    # All three hit their own entries on re-request...
    assert halo.get_halo_plan(part, g.edge_index) is flat
    assert halo.get_halo_plan(part, g.edge_index, pods=2) is default
    assert halo.get_halo_plan(part, g.edge_index, pods=2, pod_map=pm) is tuned
    # ...and an equal map ARRAY (not object) resolves to the same entry.
    assert halo.get_halo_plan(part, g.edge_index, pods=2, pod_map=pm.copy()) is tuned
    assert halo.plan_cache_stats()["size"] >= 3
    # One scoped sweep drops every flavor of this graph.
    evicted = halo.invalidate_halo_plans(
        halo.graph_fingerprint(part.n_nodes, g.edge_index, None, part.assignment)
    )
    assert evicted >= 3
    assert halo.get_halo_plan(part, g.edge_index, pods=2) is not default
    assert halo.get_halo_plan(part, g.edge_index, pods=2, pod_map=pm) is not tuned


def test_pod_map_fingerprint_distinguishes_maps():
    from repro.dist.halo import pod_map_fingerprint, validate_pod_map

    a = np.array([0, 0, 1, 1], np.int64)
    b = np.array([0, 1, 0, 1], np.int64)
    assert pod_map_fingerprint(None) == "contig"
    assert pod_map_fingerprint(a) == pod_map_fingerprint(a.copy())
    assert pod_map_fingerprint(a) != pod_map_fingerprint(b)
    with pytest.raises(ValueError):
        validate_pod_map(np.array([0, 0, 0, 1]), 4, 2)   # unbalanced
    with pytest.raises(ValueError):
        validate_pod_map(np.array([0, 0, 1, 2]), 4, 2)   # pod id out of range


# -------------------------------------------- launch CLI record (fast path)
def test_run_autotune_record_schema_small():
    """End-to-end CLI record on a small graph: calibration block empty (the
    contract), measured improvement fields present, config JSON-round-trips
    with its pods, backend and pod_map."""
    from repro.launch.autotune import run_autotune

    rec = run_autotune(n=2000, e=12000, k=8, pods=2, d_feat=64,
                       layer_dims=(64, 32, 7), shuffle_seed=None, rounds=2)
    assert rec["calibration_mismatches"] == {}
    assert rec["improvement"]["crossing_improvement"] >= 1.0
    assert rec["measured"]["autotuned"]["inter_pod_rows_crossing"] <= \
        rec["measured"]["default"]["inter_pod_rows_crossing"]
    cfg = rec["config"]
    assert cfg["pods"] == 2 and cfg["backend"] in ("segment", "bsr")
    assert sorted(np.bincount(cfg["pod_map"], minlength=2)) == [4, 4] \
        if cfg["pod_map"] is not None else True


# ----------------------------------------- 8-device 2×4 acceptance (slow)
def _run(code: str) -> None:
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])


@pytest.mark.slow
def test_autotuned_pod_map_logits_equal_default_subprocess():
    """The paper GCN on the 8-device 2×4 mesh: the autotuned pod_map plan
    produces the same logits as the default contiguous mapping (< 1e-4) —
    placement moves rows between tiers, never changes the math — while
    shipping no more inter-pod crossing rows."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.autotune import map_parts_to_pods
from repro.core.partition import partition_graph
from repro.dist.halo import build_halo_plan, relocate_node_array, restore_node_array
from repro.dist.policy import ShardingPolicy
from repro.graph.generators import citation_like
from repro.launch.mesh import make_halo_mesh
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

g = citation_like(2000, 12000, seed=1)
part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
pm = map_parts_to_pods(part, g.edge_index, 2)
default = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2)
tuned = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2, pod_map=pm)
assert tuned.inter_pod_rows_crossing <= default.inter_pod_rows_crossing
mesh = make_halo_mesh(2, 4, pod_map=pm)   # validation path; raveling unchanged
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow="feature_first")
params = gcn_init(jax.random.PRNGKey(0), cfg)
AX = ("pod", "model")

def run(plan):
    sloc, srem, sl, rl, ew = plan.device_arrays()
    xb = jnp.asarray(relocate_node_array(plan, x))
    pol0 = ShardingPolicy(comm="halo", halo_axes=AX)
    f = jax.shard_map(
        lambda fe, a, b, c, d, e: gcn_forward(
            params, fe[0], c[0], d[0], e[0], cfg,
            pol0.bind_halo(send_loc=a[0], send_rem=b[0]))[None],
        mesh=mesh, in_specs=(P(AX),) * 6, out_specs=P(AX), check_vma=False,
    )
    return restore_node_array(plan, np.asarray(f(xb, sloc, srem, sl, rl, ew)))

err = np.abs(run(tuned) - run(default)).max()
assert err < 1e-4, err
print("OK", err)
"""
    _run(code)
