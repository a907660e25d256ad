"""Incremental halo replan + scoped invalidation for mutating graphs
(`repro.dist.delta`), pinned by the delta-vs-rebuild differential harness
(tests/_delta_oracle.py): random mutation sequences where EVERY step asserts
the incrementally repaired plan equals a from-scratch `build_halo_plan`
(export segments, pads, sender encodings, masks, numpy-emulated exchange +
aggregation) and the tile-patched blocked adjacencies equal a re-block —
flat and hierarchical, 1 and 8 devices, plus the plan-cache versioned
re-key / scoped-eviction contracts and the elastic pure-resize regression.

`--delta-seed N` (tests/conftest.py) re-seeds the long mutation runs.
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import _delta_oracle as O
from repro.core.partition import partition_graph
from repro.dist import halo
from repro.dist.delta import (
    DeltaPlanner,
    GraphDelta,
    RelocalizePolicy,
    apply_delta_to_graph,
    delta_update_blocked_adjacency,
)
from repro.dist.halo import (
    build_halo_plan,
    cached_halo_plan,
    invalidate_halo_plans,
    plan_blocked_adjacency,
    plan_cache_stats,
    plan_split_blocked_adjacency,
    register_halo_plan,
)
from repro.graph.generators import citation_like
from repro.graph.structure import blocked_adjacency
from repro.kernels.bsr_spmm import poison_padding
from repro.kernels.ops import bsr_spmm
from repro.train.elastic import elastic_replan

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _mk(n, e, k, seed, *, refine=False):
    g = citation_like(n, e, seed=seed)
    w = (0.1 + np.random.default_rng(seed).random(g.n_edges)).astype(np.float32)
    part = partition_graph(n, g.edge_index, k, method="bfs", seed=seed, refine=refine)
    return g, w, part


def _plan_fields_equal(a, b):
    for f in ("send_idx", "senders_l", "receivers_l", "edge_w", "perm",
              "part_sizes", "send_loc", "send_rem"):
        x, y = getattr(a, f), getattr(b, f)
        if x is None:
            assert y is None, f
            continue
        assert np.array_equal(x, y), f
    assert (a.s_max, a.s_loc, a.s_rem, a.e_local, a.n_local, a.axes, a.n_pods) \
        == (b.s_max, b.s_loc, b.s_rem, b.e_local, b.n_local, b.axes, b.n_pods)


# ---------------------------------------------------------- v0 == build_halo
def test_v0_plans_bit_identical_to_builder():
    """Before any delta, the planner's plans must be BIT-identical to
    `build_halo_plan` — same slot layout, same padding, same arrays — for
    the flat and the hierarchical schedule (the whole differential harness
    leans on this anchor)."""
    g, w, part = _mk(128, 700, 4, seed=3)
    pl = DeltaPlanner(part, g.edge_index, w)
    _plan_fields_equal(pl.plan(), build_halo_plan(part, g.edge_index, w))
    _plan_fields_equal(
        pl.plan(axes=("pod", "model"), pods=2),
        build_halo_plan(part, g.edge_index, w, axes=("pod", "model"), pods=2))


# ------------------------------------------------- random-mutation sequences
def _mutation_run(n, e, k, seed, steps, schedules, max_ops=8):
    g, w, part = _mk(n, e, k, seed=seed)
    pl = DeltaPlanner(part, g.edge_index, w)
    plans = [pl.plan(axes=axes, pods=pods) for axes, pods in schedules]
    ei, ww = g.edge_index.astype(np.int64), w
    rng = np.random.default_rng(seed + 1)
    for _ in range(steps):
        d = O.random_delta(rng, n, ei, max_ops=max_ops)
        pl.apply(d)
        ei, ww = O.apply_delta_to_edges(ei, ww, d)
        assert pl.n_edges == ei.shape[1]
        for p in plans:
            O.assert_plan_matches_rebuild(p, part, ei, ww)
    return pl, plans, part, ei, ww


@settings(max_examples=5, deadline=None)
@given(n=st.integers(48, 140), e=st.integers(120, 600),
       k=st.sampled_from([2, 4]), seed=st.integers(0, 30))
def test_delta_vs_rebuild_flat_random_sequences(n, e, k, seed):
    _mutation_run(n, e, k, seed, steps=6, schedules=[(("model",), 1)])


@settings(max_examples=5, deadline=None)
@given(n=st.integers(64, 160), e=st.integers(200, 700), seed=st.integers(0, 30))
def test_delta_vs_rebuild_hier_random_sequences(n, e, seed):
    _mutation_run(n, e, 4, seed, steps=6,
                  schedules=[(("pod", "model"), 2)])


def test_delta_200_step_acceptance(delta_seed):
    """The headline acceptance: 200+ random mutation steps on one planner
    holding a flat AND a hierarchical plan, every step differentially
    checked against a rebuild, blocked tables checked against a re-block at
    checkpoints. Reseedable via ``--delta-seed``."""
    seed = 1000 + delta_seed
    n, e, k, blk = 192, 1200, 4, 32
    g, w, part = _mk(n, e, k, seed=seed % 97)
    pl = DeltaPlanner(part, g.edge_index, w)
    plans = [pl.plan(), pl.plan(axes=("pod", "model"), pods=2)]
    for p in plans:
        plan_blocked_adjacency(p, blk)
        plan_split_blocked_adjacency(p, blk)
    ei, ww = g.edge_index.astype(np.int64), w
    rng = np.random.default_rng(seed)
    patched = dropped = 0
    for step in range(200):
        d = O.random_delta(rng, n, ei, max_ops=10)
        rep = pl.apply(d)
        patched += rep["blocked_patched"]
        dropped += rep["blocked_dropped"]
        ei, ww = O.apply_delta_to_edges(ei, ww, d)
        for p in plans:
            O.assert_plan_matches_rebuild(p, part, ei, ww)
        if step % 25 == 24:
            for p in plans:
                mine_c = plan_blocked_adjacency(p, blk)
                mine_i, mine_b = plan_split_blocked_adjacency(p, blk)
                fresh = dataclasses.replace(p)         # empty blocked cache
                O.assert_blocked_matches(mine_c, plan_blocked_adjacency(fresh, blk))
                ref_i, ref_b = plan_split_blocked_adjacency(fresh, blk)
                O.assert_blocked_matches(mine_i, ref_i)
                O.assert_blocked_matches(mine_b, ref_b)
    assert patched > 0, "no blocked table was ever tile-patched"
    assert pl.version == 200
    assert pl.graph_key.endswith("@d200")


# ------------------------------------------- maintenance soak + acceptance
def _w_of(ei):
    """Weight as a pure function of (u, v): duplicate edge instances share
    it, so after a re-localization reorders the planner's internal slots a
    delete can never consume a 'different-weight' duplicate than the numpy
    oracle does (same trick as the 8-device prelude)."""
    ei = np.asarray(ei, np.int64)
    return (0.1 + (ei[0] * 131 + ei[1] * 17) % 97 / 97.0).astype(np.float32)


def _maintenance_delta(rng, n, ei, max_ops=8):
    d = O.random_delta(rng, n, ei, max_ops=max_ops)
    return dataclasses.replace(d, insert_w=_w_of(d.edge_inserts))


@settings(max_examples=4, deadline=None)
@given(n=st.integers(140, 220), e=st.integers(500, 1000), seed=st.integers(0, 30))
def test_soak_interleaved_maintenance_random_sequences(n, e, seed):
    """Soak: random mutation batches interleaved with `compact()` and both
    FORCED and THRESHOLD-driven re-localizations, the full delta oracle
    after every single step. Post-relocalize the oracle rebuilds against
    the planner's OWN (re-localized) partition — plans must stay equal to
    a from-scratch build at every interleaving point."""
    g, w, part = _mk(n, e, 4, seed=seed)
    w = _w_of(g.edge_index)
    pol = RelocalizePolicy(threshold=1.01, patience=2, cooldown=2, block=32)
    pl = DeltaPlanner(part, g.edge_index, w, relocalize_policy=pol)
    plans = [pl.plan(), pl.plan(axes=("pod", "model"), pods=2)]
    ei, ww = g.edge_index.astype(np.int64), w
    rng = np.random.default_rng(seed + 17)
    for step in range(12):
        act = step % 6
        if act == 4:
            pl.compact()
        elif act == 5:
            pl.relocalize(block=32)
            assert pl.locality_drift(32)["drift_ratio"] == 1.0
        else:
            d = _maintenance_delta(rng, n, ei, max_ops=8)
            pl.apply(d)                  # may auto-relocalize via the policy
            ei, ww = O.apply_delta_to_edges(ei, ww, d)
        assert pl.n_edges == ei.shape[1]
        for p in plans:
            O.assert_plan_matches_rebuild(p, pl.part, ei, ww)


def test_delta_200_step_acceptance_with_maintenance(delta_seed):
    """The ISSUE 9 acceptance twin of the 200-step run: same mutation load,
    but with the relocalize policy armed and periodic compaction — the
    oracle must hold after every step, drift must come back to exactly 1.0
    at each fire, and maintenance must actually have fired."""
    seed = 2000 + delta_seed
    n, e, k, blk = 192, 1200, 4, 32
    g, w, part = _mk(n, e, k, seed=seed % 97)
    w = _w_of(g.edge_index)
    pol = RelocalizePolicy(threshold=1.02, patience=3, cooldown=8, block=blk)
    pl = DeltaPlanner(part, g.edge_index, w, relocalize_policy=pol)
    plans = [pl.plan(), pl.plan(axes=("pod", "model"), pods=2)]
    ei, ww = g.edge_index.astype(np.int64), w
    rng = np.random.default_rng(seed)
    fired = compacts = 0
    for step in range(200):
        if step % 50 == 49:
            compacts += bool(pl.compact()["changed"])
        d = _maintenance_delta(rng, n, ei, max_ops=10)
        rep = pl.apply(d)
        if rep["relocalized"] is not None:
            fired += 1
            assert pl.locality_drift(blk)["drift_ratio"] == 1.0
        ei, ww = O.apply_delta_to_edges(ei, ww, d)
        for p in plans:
            O.assert_plan_matches_rebuild(p, pl.part, ei, ww)
    assert fired >= 1, "200 uniform-insert steps never crossed the threshold"
    assert pl.version >= 200 + fired
    assert pl.n_edges == ei.shape[1]


# ------------------------------------------------------ blocked tables (bsr)
def test_patched_plan_blocked_spmm_and_poison(delta_seed):
    """Patched vs re-blocked per-shard tables through the REAL ragged
    kernel: same `bsr_spmm` output, and a poisoned-padding run proves the
    kernel never reads tombstoned/padding tiles (NaN would propagate)."""
    n, e, k = 256, 1600, 4
    g, w, part = _mk(n, e, k, seed=5)
    pl = DeltaPlanner(part, g.edge_index, w)
    plan = pl.plan()
    plan_blocked_adjacency(plan, 128)
    ei, ww = g.edge_index.astype(np.int64), w
    rng = np.random.default_rng(200 + delta_seed)
    rep = None
    for _ in range(8):
        d = O.random_delta(rng, n, ei, max_ops=12)
        rep = pl.apply(d)
        ei, ww = O.apply_delta_to_edges(ei, ww, d)
    mine = plan_blocked_adjacency(plan, 128)
    ref = plan_blocked_adjacency(dataclasses.replace(plan), 128)
    O.assert_blocked_matches(mine, ref)
    z = rng.standard_normal((mine.n_cols, 128)).astype(np.float32)
    poisoned = poison_padding(mine.vals, mine.cols, mine.lens)
    for b in range(k):
        out = np.asarray(bsr_spmm(
            jnp.asarray(mine.vals[b]), jnp.asarray(mine.cols[b]),
            jnp.asarray(z), lens=jnp.asarray(mine.lens[b])))
        out_ref = np.asarray(bsr_spmm(
            jnp.asarray(ref.vals[b]), jnp.asarray(ref.cols[b]),
            jnp.asarray(z), lens=jnp.asarray(ref.lens[b])))
        assert np.abs(out - out_ref).max() < 1e-4
        out_poison = np.asarray(bsr_spmm(
            jnp.asarray(poisoned[b]), jnp.asarray(mine.cols[b]),
            jnp.asarray(z), lens=jnp.asarray(mine.lens[b])))
        assert np.isfinite(out_poison).all(), "kernel read a poisoned tile"
        assert np.abs(out_poison - out).max() == 0.0


def test_delta_update_global_blocked_adjacency(delta_seed):
    """The standalone `BlockedAdjacency` patch path: 30 random deltas,
    densified equality against a re-block each step; T only ever grows, and
    grows geometrically."""
    g = citation_like(200, 900, seed=2)
    w = (0.1 + np.random.default_rng(1).random(g.n_edges)).astype(np.float32)
    g = dataclasses.replace(g, edge_weight=w)
    blk = 16
    ba = blocked_adjacency(g.n_nodes, g.edge_index, g.edge_weight, blk)
    rng = np.random.default_rng(9 + delta_seed)
    t_hist = [ba.max_nnzb]
    for _ in range(30):
        d = O.random_delta(rng, g.n_nodes, g.edge_index, max_ops=10)
        g = apply_delta_to_graph(g, d)
        ba = delta_update_blocked_adjacency(ba, g.edge_index, g.edge_weight, d)
        t_hist.append(ba.max_nnzb)
        ref = blocked_adjacency(g.n_nodes, g.edge_index, g.edge_weight, blk)
        dm = O.densify(ba.block_vals, ba.block_cols, ba.row_nnzb,
                       g.n_nodes, ba.n_col_nodes)
        dr = O.densify(ref.block_vals, ref.block_cols, ref.row_nnzb,
                       g.n_nodes, ref.n_col_nodes)
        assert np.abs(dm - dr).max() < 1e-5
    assert all(b >= a for a, b in zip(t_hist, t_hist[1:])), "T shrank"


def test_tombstone_then_poison_padding_zeroes():
    """A delta that empties a whole tile must tombstone it: the freed slot
    is zeroed, lens drops, and `poison_padding` covers it (the kernel-side
    never-read proof for the swap-removed slot)."""
    # two edges in one tile, one edge in another → delete the lone edge
    ei = np.asarray([[0, 1, 40], [0, 0, 0]], np.int64)
    ba = blocked_adjacency(64, ei, None, 32, n_col_nodes=64)
    assert int(ba.row_nnzb[0]) == 2
    d = GraphDelta(edge_deletes=np.asarray([[40], [0]]))
    g = dataclasses.replace(
        citation_like(64, 4, seed=0), edge_index=ei, edge_weight=None)
    g2 = apply_delta_to_graph(g, d)
    ba = delta_update_blocked_adjacency(ba, g2.edge_index, g2.edge_weight, d)
    assert int(ba.row_nnzb[0]) == 1
    assert not ba.block_vals[0, 1:].any(), "tombstoned slot not zeroed"
    pz = poison_padding(ba.block_vals, ba.block_cols, ba.row_nnzb)
    assert np.isnan(pz[0, 1]).all() and not np.isnan(pz[0, 0]).any()


def test_append_into_full_row_with_tombstone_same_delta():
    """Regression: a row block at exact tile capacity gets an append AND a
    tombstone in ONE delta. The net count fits, but replaying the append
    before the tombstone transiently overflows the table — the patcher must
    order tombstones first and size capacity on the running peak, so this
    must go through without growing T."""
    # row block 0 at capacity T=2 (col tiles 0 and 1, exact-fit build)
    ei = np.asarray([[0, 40], [0, 0]], np.int64)
    ba = blocked_adjacency(96, ei, None, 32, n_col_nodes=96)
    assert ba.max_nnzb == 2 and int(ba.row_nnzb[0]) == 2
    # one delta: empty col tile 1 (tombstone) + open col tile 2 (append)
    d = GraphDelta(edge_deletes=np.asarray([[40], [0]]),
                   edge_inserts=np.asarray([[70], [0]]))
    g = dataclasses.replace(
        citation_like(96, 4, seed=0), edge_index=ei, edge_weight=None)
    g2 = apply_delta_to_graph(g, d)
    ba = delta_update_blocked_adjacency(ba, g2.edge_index, g2.edge_weight, d)
    assert ba.max_nnzb == 2, "transient overflow forced a spurious T growth"
    assert int(ba.row_nnzb[0]) == 2
    ref = blocked_adjacency(96, g2.edge_index, g2.edge_weight, 32,
                            n_col_nodes=96)
    dm = O.densify(ba.block_vals, ba.block_cols, ba.row_nnzb, 96, 96)
    dr = O.densify(ref.block_vals, ref.block_cols, ref.row_nnzb, 96, 96)
    assert np.abs(dm - dr).max() < 1e-5


# -------------------------------------------------------- plan-cache re-key
def test_versioned_rekey_old_key_misses_new_key_hits():
    g, w, part = _mk(96, 500, 4, seed=7)
    invalidate_halo_plans()
    halo.reset_plan_cache_stats()
    pl = DeltaPlanner(part, g.edge_index, w)
    p = pl.plan()
    key0 = pl.graph_key
    assert cached_halo_plan(key0, 4, "model", builder=_boom) is p  # hit
    rep = pl.apply(GraphDelta(edge_inserts=np.asarray([[1], [90]])))
    assert rep["stale_keys_evicted"] == 1
    key1 = pl.graph_key
    assert key1 != key0 and key1.endswith("@d1")
    # new key hits the SAME repaired object; stale key re-runs the builder
    assert cached_halo_plan(key1, 4, "model", builder=_boom) is p
    with pytest.raises(RuntimeError, match="rebuilt"):
        cached_halo_plan(key0, 4, "model", builder=_boom)
    assert plan_cache_stats()["evictions"] >= 1


def _boom():
    raise RuntimeError("builder re-ran on what should be a cache hit (rebuilt)")


def test_rekey_covers_every_schedule_flavor():
    """One planner holding flat + hierarchical plans migrates ALL of them in
    one apply — each flavor's new key hits, each old key is gone."""
    g, w, part = _mk(96, 500, 4, seed=8)
    invalidate_halo_plans()
    pl = DeltaPlanner(part, g.edge_index, w)
    flat = pl.plan()
    hier = pl.plan(axes=("pod", "model"), pods=2)
    key0 = pl.graph_key
    rep = pl.apply(GraphDelta(edge_deletes=g.edge_index[:, :1]))
    assert rep["stale_keys_evicted"] == 2
    key1 = pl.graph_key
    assert cached_halo_plan(key1, 4, "model", builder=_boom) is flat
    assert cached_halo_plan(key1, 4, ("pod", "model"), pods=2,
                            builder=_boom) is hier
    for axes, pods in (("model", 1), (("pod", "model"), 2)):
        with pytest.raises(RuntimeError):
            cached_halo_plan(key0, 4, axes, pods=pods, builder=_boom)


# --------------------------------------------------- scoped cache eviction
def test_scoped_invalidation_spans_hier_flavors_and_spares_others():
    """`invalidate_halo_plans(graph_key)` drops EVERY (axes, n_pods) flavor
    of that graph — flat, 2-pod, 4-pod — in one call, while another graph's
    plans coexist untouched (the miss case)."""
    g, w, part = _mk(96, 500, 8, seed=9)
    g2, w2, part2 = _mk(96, 500, 8, seed=10)
    invalidate_halo_plans()
    a = build_halo_plan(part, g.edge_index, w)
    register_halo_plan("graph-a", 8, "model", plan=a)
    register_halo_plan("graph-a", 8, ("pod", "model"), pods=2,
                       plan=build_halo_plan(part, g.edge_index, w,
                                            axes=("pod", "model"), pods=2))
    register_halo_plan("graph-a", 8, ("pod", "model"), pods=4,
                       plan=build_halo_plan(part, g.edge_index, w,
                                            axes=("pod", "model"), pods=4))
    b = build_halo_plan(part2, g2.edge_index, w2)
    register_halo_plan("graph-b", 8, "model", plan=b)
    assert invalidate_halo_plans("graph-a") == 3
    assert cached_halo_plan("graph-b", 8, "model", builder=_boom) is b
    with pytest.raises(RuntimeError):
        cached_halo_plan("graph-a", 8, "model", builder=_boom)
    # k-scoped narrowing: a k=4 eviction leaves the k=8 entry alone
    register_halo_plan("graph-b", 4, "model", plan=b)
    assert invalidate_halo_plans("graph-b", k=4) == 1
    assert cached_halo_plan("graph-b", 8, "model", builder=_boom) is b
    invalidate_halo_plans()


# ------------------------------------------------------------------ elastic
def test_elastic_pure_resize_keeps_plans_zero_evictions():
    """Satellite regression: an elastic resize that preserves the
    model-parallel degree must not evict a single cached plan."""
    g, w, part = _mk(96, 500, 4, seed=11)
    invalidate_halo_plans()
    halo.reset_plan_cache_stats()
    register_halo_plan("elastic-g", 4, "model",
                       plan=build_halo_plan(part, g.edge_index, w))
    before = plan_cache_stats()
    plan = elastic_replan(12, 4, graph_key="elastic-g")   # data 4 → 3
    assert plan.shape == (3, 4)
    assert plan_cache_stats()["evictions"] == before["evictions"] == 0
    assert cached_halo_plan("elastic-g", 4, "model", builder=_boom) is not None


def test_elastic_model_halving_evicts_only_that_graph():
    g, w, part = _mk(96, 500, 4, seed=12)
    g2, w2, part2 = _mk(96, 500, 4, seed=13)
    invalidate_halo_plans()
    register_halo_plan("shrinks", 4, "model",
                       plan=build_halo_plan(part, g.edge_index, w))
    register_halo_plan("shrinks", 4, ("pod", "model"), pods=2,
                       plan=build_halo_plan(part, g.edge_index, w,
                                            axes=("pod", "model"), pods=2))
    survivor = build_halo_plan(part2, g2.edge_index, w2)
    register_halo_plan("survives", 4, "model", plan=survivor)
    plan = elastic_replan(3, 4, graph_key="shrinks")      # m 4 → 2: repartition
    assert plan.shape == (1, 2)
    with pytest.raises(RuntimeError):
        cached_halo_plan("shrinks", 4, "model", builder=_boom)
    assert cached_halo_plan("survives", 4, "model", builder=_boom) is survivor
    invalidate_halo_plans()


# --------------------------------------------------------------- validation
def test_graph_delta_validation_errors():
    d = GraphDelta(edge_inserts=np.asarray([[5], [99]]))
    with pytest.raises(ValueError, match="outside"):
        d.validate(50)
    with pytest.raises(ValueError, match="insert_w length"):
        GraphDelta(edge_inserts=np.asarray([[1], [2]]),
                   insert_w=np.asarray([1.0, 2.0])).validate(10)
    with pytest.raises(ValueError, match="> 0"):
        GraphDelta(edge_inserts=np.asarray([[1], [2]]),
                   insert_w=np.asarray([0.0])).validate(10)
    with pytest.raises(ValueError, match="feature_values"):
        GraphDelta(feature_touches=np.asarray([1, 2]),
                   feature_values=np.zeros((1, 4), np.float32)).validate(10)
    with pytest.raises(ValueError, match="\\(2, E\\)"):
        GraphDelta(edge_inserts=np.zeros((3, 2)))
    assert GraphDelta.empty().is_empty
    assert GraphDelta(edge_inserts=np.asarray([[1], [2]])).n_ops == 1


def test_absent_delete_raises_everywhere():
    g, w, part = _mk(64, 300, 2, seed=14)
    d = GraphDelta(edge_deletes=np.asarray([[63], [62]]))
    if ((g.edge_index[0] == 63) & (g.edge_index[1] == 62)).any():
        pytest.skip("generator produced the edge this test needs absent")
    with pytest.raises(ValueError, match="absent"):
        apply_delta_to_graph(g, d)
    pl = DeltaPlanner(part, g.edge_index, w)
    with pytest.raises(ValueError, match="absent"):
        pl.apply(d)


def test_apply_delta_to_graph_is_order_preserving():
    g = citation_like(30, 60, 8, 3, seed=1)
    keep_before = [tuple(c) for c in g.edge_index.T.tolist()]
    victim = keep_before[10]
    d = GraphDelta(edge_deletes=np.asarray([[victim[0]], [victim[1]]]),
                   edge_inserts=np.asarray([[3], [4]]),
                   feature_touches=np.asarray([7]),
                   feature_values=np.full((1, 8), 5.0, np.float32))
    g2 = apply_delta_to_graph(g, d)
    after = [tuple(c) for c in g2.edge_index.T.tolist()]
    expect = [c for i, c in enumerate(keep_before) if i != 10] + [(3, 4)]
    assert after == expect, "deletes must compact and inserts must append"
    assert np.allclose(g2.features[7], 5.0)
    same = (g2.features == np.asarray(g.features)).all(axis=1)
    assert not same[7] and same[np.arange(30) != 7].all(), (
        "exactly the touched feature row must change")
    assert g2.features is not g.features


# ------------------------------------------------ 8-device mid-training run
def _run(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
    return out.stdout


_PRELUDE = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_graph
from repro.dist.delta import DeltaPlanner, GraphDelta
from repro.dist.halo import relocate_node_array, restore_node_array
from repro.graph.generators import citation_like
from repro.launch.mesh import make_mesh

def w_of(ei):
    # weight = pure function of (u, v): duplicate edge instances share it,
    # so the delta path and the oracle edge list can never disagree on w
    return (0.1 + (ei[0] * 131 + ei[1] * 17) % 97 / 97.0).astype(np.float32)

g = citation_like(400, 2400, seed=5)
ei = g.edge_index.astype(np.int64)
part = partition_graph(g.n_nodes, ei, 8, method="bfs", seed=0, refine=True)
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
"""


@pytest.mark.slow
def test_delta_replan_mid_training_8dev_subprocess():
    """8-device acceptance: run the halo forward, mutate the graph through
    the planner mid-run, and check the repaired plan's sharded exchange +
    aggregation still matches the global reference on the NEW edges — for
    the flat AND the hierarchical schedule, without rebuilding a plan."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init
from repro.dist.policy import NO_POLICY, ShardingPolicy

pl = DeltaPlanner(part, ei, w_of(ei))
plans = {"flat": pl.plan(), "hier": pl.plan(axes=("pod", "model"), pods=2)}
mesh1d = make_mesh((8,), ("model",))
mesh2d = make_mesh((2, 4), ("pod", "model"))
AX = ("pod", "model")
cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow="feature_first")
params = gcn_init(jax.random.PRNGKey(0), cfg)

def fwd(fe, pol, s, r, ww):
    return gcn_forward(params, fe, s, r, ww, cfg, pol)

def sharded_forward(plan):
    xb = jnp.asarray(relocate_node_array(plan, x))
    if plan.is_hierarchical:
        sloc, srem, sl, rl, ew = plan.device_arrays()
        pol0 = ShardingPolicy(comm="halo", halo_axes=AX)
        f = jax.shard_map(
            lambda fe, a, b, c, d, e: fwd(
                fe[0], pol0.bind_halo(send_loc=a[0], send_rem=b[0]),
                c[0], d[0], e[0])[None],
            mesh=mesh2d, in_specs=(P(AX),) * 6, out_specs=P(AX), check_vma=False,
        )
        out = f(xb, sloc, srem, sl, rl, ew)
    else:
        si, sl, rl, ew = plan.device_arrays()
        pol0 = ShardingPolicy(comm="halo")
        f = jax.shard_map(
            lambda fe, a, b, c, d: fwd(fe[0], pol0.bind_halo(a[0]),
                                       b[0], c[0], d[0])[None],
            mesh=mesh1d, in_specs=(P("model"),) * 5, out_specs=P("model"),
            check_vma=False,
        )
        out = f(xb, si, sl, rl, ew)
    return restore_node_array(plan, np.asarray(out))

def global_ref(ei):
    return np.asarray(gcn_forward(
        params, jnp.asarray(x), jnp.asarray(ei[0]), jnp.asarray(ei[1]),
        jnp.asarray(w_of(ei)), cfg, NO_POLICY))

# pre-delta: both schedules match the global forward
ref = global_ref(ei)
for name, plan in plans.items():
    got = sharded_forward(plan)
    assert np.abs(got - ref).max() < 1e-4, ("pre", name)

# mid-training mutation: delete 40 edges, insert 40 new ones
rng = np.random.default_rng(3)
drop = rng.choice(ei.shape[1], 40, replace=False)
ins = rng.integers(0, g.n_nodes, (2, 40))
delta = GraphDelta(edge_inserts=ins, edge_deletes=ei[:, drop],
                   insert_w=w_of(ins))
rep = pl.apply(delta)
assert rep["senders_remapped"] > 0
keep = np.ones(ei.shape[1], bool); keep[drop] = False
ei2 = np.concatenate([ei[:, keep], ins], axis=1)
assert pl.n_edges == ei2.shape[1]

ref2 = global_ref(ei2)
assert np.abs(ref2 - ref).max() > 1e-3, "delta too weak to detect staleness"
for name, plan in plans.items():
    got = sharded_forward(plan)
    assert np.abs(got - ref2).max() < 1e-4, ("post", name, np.abs(got - ref2).max())
print("OK")
"""
    _run(code)
