"""Online locality maintenance (`repro.dist.delta`): RelocalizePolicy
hysteresis, drift-triggered in-place re-localization, and pad compaction —
pinned end-to-end by the delta differential oracle (tests/_delta_oracle.py)
and by bit-identity against a from-scratch `build_halo_plan`.

Contracts (ISSUE 9 acceptance):
  * `drift_ratio == 1.0` EXACTLY immediately after any re-localization
    (the drift reference order is a pure function of the edge multiset),
  * hysteresis: no fire below threshold, fire only after K consecutive
    exceedances, no double-fire inside the cooldown window,
  * `compact()` on an untouched v0 planner is a no-op (plans stay
    bit-identical to the builder, version unchanged); after churn it
    shrinks pads back to occupancy and lands bit-identical to a rebuild,
  * the fresh-reorder term of `locality_drift` runs ONE BFS per structural
    era (memo regression), and
  * live state — optimizer moments via `relocate_state_tree`, serve-cache
    residents via scoped invalidation + `adopt_partition` — survives a
    re-localization with forward results equal modulo row order (8-device
    subprocess + serve-engine variant).
"""
import numpy as np
import pytest

import _delta_oracle as O
from test_graph_delta import _PRELUDE, _boom, _mk, _plan_fields_equal, _run
from repro.dist.delta import (
    DeltaPlanner,
    GraphDelta,
    RelocalizePolicy,
    _relocalized_assignment,
)
from repro.dist.halo import (
    build_halo_plan,
    cached_halo_plan,
    invalidate_halo_plans,
    plan_blocked_adjacency,
    plan_layout,
)
from repro.graph.generators import citation_like
from repro.train.elastic import relocate_state_tree


def _churn(pl, rng, rounds=6, frac=0.02, members=20):
    """Severed-ties churn: delete edges incident to a member set, reinsert
    the same count internal to it — degrades locality without changing E."""
    for _ in range(rounds):
        ei = pl.edge_index()
        m = max(int(ei.shape[1] * frac), 2)
        mem = rng.choice(pl.n, members, replace=False)
        inc = np.flatnonzero(np.isin(ei[0], mem) | np.isin(ei[1], mem))[:m]
        if inc.size == 0:
            continue
        s = mem[rng.integers(0, mem.size, inc.size)]
        d = mem[rng.integers(0, mem.size, inc.size)]
        bad = s == d
        d[bad] = mem[(np.searchsorted(np.sort(mem), d[bad]) + 1) % mem.size]
        pl.apply(GraphDelta(edge_inserts=np.stack([s, d]),
                            edge_deletes=ei[:, inc],
                            insert_w=np.full(inc.size, 0.5, np.float32)))


# -------------------------------------------------------------- hysteresis
def test_policy_below_threshold_never_fires():
    pol = RelocalizePolicy(threshold=1.5, patience=2, cooldown=3)
    assert not any(pol.observe(r) for r in [0.9, 1.0, 1.4, 1.5, 1.49] * 4), (
        "ratios at or below threshold must never trigger")
    assert pol.streak == 0


def test_policy_fires_after_k_consecutive_and_dip_resets():
    pol = RelocalizePolicy(threshold=1.2, patience=3, cooldown=0)
    got = [pol.observe(r) for r in [1.3, 1.3, 1.1, 1.3, 1.3, 1.3]]
    assert got == [False, False, False, False, False, True], (
        "a dip below threshold must reset the consecutive-exceedance streak")


def test_policy_cooldown_blocks_double_fire():
    pol = RelocalizePolicy(threshold=1.0, patience=1, cooldown=3)
    got = [pol.observe(9.0) for _ in range(6)]
    # fire, then 3 cooldown observations are swallowed, then re-arm + fire
    assert got == [True, False, False, False, True, False]


# ----------------------------------------------- drift == 1.0 after reorder
def test_drift_ratio_exactly_one_after_relocalize():
    """The drift reference is canonicalized over the edge MULTISET, so the
    order relocalize installs IS the reference order: the ratio must come
    back 1.0 exactly (not ≈) for the same (block, method)."""
    g, w, part = _mk(300, 1800, 4, seed=6)
    pl = DeltaPlanner(part, g.edge_index, w)
    pl.plan()
    _churn(pl, np.random.default_rng(0), rounds=5)
    assert pl.locality_drift(32)["drift_ratio"] > 1.0
    rep = pl.relocalize(block=32)
    assert rep["executed_tiles_after"] <= rep["executed_tiles_before"]
    assert pl.locality_drift(32)["drift_ratio"] == 1.0
    # edge order itself is irrelevant: a shuffled copy of the same multiset
    # yields the same reference assignment
    ei = pl.edge_index()
    shuf = ei[:, np.random.default_rng(1).permutation(ei.shape[1])]
    np.testing.assert_array_equal(
        _relocalized_assignment(pl.n, ei, pl.k, block=32),
        _relocalized_assignment(pl.n, shuf, pl.k, block=32))


def test_relocalize_bit_identical_to_fresh_build_and_rekeys():
    g, w, part = _mk(256, 1500, 4, seed=8)
    invalidate_halo_plans()
    pl = DeltaPlanner(part, g.edge_index, w)
    p = pl.plan()
    h = pl.plan(axes=("pod", "model"), pods=2)
    _churn(pl, np.random.default_rng(2), rounds=4)
    key0, v0 = pl.graph_key, pl.version
    pl.relocalize(block=64)
    assert pl.version == v0 + 1 and pl.graph_key != key0
    # the repaired objects ARE the builder's output on the new partition
    ei, ww = pl.edge_index(), pl.edge_weights()
    _plan_fields_equal(p, build_halo_plan(pl.part, ei, ww))
    _plan_fields_equal(h, build_halo_plan(pl.part, ei, ww,
                                          axes=("pod", "model"), pods=2))
    for q in (p, h):
        O.assert_plan_matches_rebuild(q, pl.part, ei, ww)
    # versioned re-key: new key hits the SAME objects, old key is gone
    assert cached_halo_plan(pl.graph_key, 4, "model", builder=_boom) is p
    with pytest.raises(RuntimeError):
        cached_halo_plan(key0, 4, "model", builder=_boom)
    invalidate_halo_plans()


def test_policy_fires_through_apply_and_reports():
    g, w, part = _mk(300, 1800, 4, seed=9)
    pol = RelocalizePolicy(threshold=1.01, patience=2, cooldown=2, block=32)
    pl = DeltaPlanner(part, g.edge_index, w, relocalize_policy=pol)
    pl.plan()
    fired = 0
    rng = np.random.default_rng(3)
    for _ in range(12):
        before = pl.version
        _churn(pl, rng, rounds=1, frac=0.03)
        if pl.version > before + 1:           # apply bump + relocalize bump
            fired += 1
    assert fired >= 1, "threshold-driven relocalization never fired"
    # the report plumbs through apply()
    pl2 = DeltaPlanner(part, g.edge_index, w,
                       relocalize_policy=RelocalizePolicy(
                           threshold=0.0, patience=1, cooldown=0, block=32))
    ei = pl2.edge_index()
    rep = pl2.apply(GraphDelta(edge_deletes=ei[:, :1]))
    r = rep["relocalized"]
    assert r is not None and r["version"] == pl2.version
    assert rep["graph_key"] == pl2.graph_key == r["graph_key"]
    assert pl2.locality_drift(32)["drift_ratio"] == 1.0


# ------------------------------------------------------------------ compact
def test_compact_on_v0_planner_is_noop():
    g, w, part = _mk(128, 700, 4, seed=3)
    pl = DeltaPlanner(part, g.edge_index, w)
    p = pl.plan()
    h = pl.plan(axes=("pod", "model"), pods=2)
    plan_blocked_adjacency(p, 32)
    key0, v0 = pl.graph_key, pl.version
    rep = pl.compact()
    assert not rep["changed"] and not rep["rebuilt"]
    assert rep["bytes_reclaimed"] == 0
    assert not any(rep["pad_rows_reclaimed"].values())
    assert (pl.graph_key, pl.version) == (key0, v0)
    # builder-tight means builder-identical, still
    _plan_fields_equal(p, build_halo_plan(part, g.edge_index, w))
    _plan_fields_equal(h, build_halo_plan(part, g.edge_index, w,
                                          axes=("pod", "model"), pods=2))


def test_compact_after_churn_reclaims_and_matches_builder():
    g, w, part = _mk(256, 1500, 4, seed=11)
    pl = DeltaPlanner(part, g.edge_index, w)
    p = pl.plan()
    rng = np.random.default_rng(5)
    # grow pads (cut inserts), then delete most of them → loose high water
    a = pl.part.assignment
    src = np.flatnonzero(a == 0)[:40].astype(np.int64)
    dst = np.full(src.size, int(np.flatnonzero(a == 1)[0]), np.int64)
    grow = GraphDelta(edge_inserts=np.stack([src, dst]))
    pl.apply(grow)
    pl.apply(GraphDelta(edge_deletes=np.stack([src, dst])[:, :36]))
    ei, ww = pl.edge_index(), pl.edge_weights()
    occ_loose = pl.pad_occupancy()
    rep = pl.compact()
    assert rep["changed"] and rep["rebuilt"]
    assert rep["bytes_reclaimed"] > 0
    assert sum(rep["pad_rows_reclaimed"].values()) > 0
    # compacting removes capacity, never occupancy → utilization rises
    assert pl.pad_occupancy()["frac"] >= occ_loose["frac"]
    _plan_fields_equal(p, build_halo_plan(pl.part, ei, ww))
    O.assert_plan_matches_rebuild(p, pl.part, ei, ww)
    # idempotent: a second compact finds everything tight already
    assert not pl.compact()["changed"]


# ------------------------------------------------------- drift memo (fix)
def test_drift_fresh_reorder_memoized_per_structural_era(monkeypatch):
    """Regression: `apply(measure_drift=True)` used to re-run the reorder
    BFS on EVERY apply. The fresh term is a pure function of the edge
    multiset between structural changes, so non-structural applies must
    reuse one memoized BFS; pad growth / relocalize open a new era."""
    import repro.graph.structure as S

    g, w, part = _mk(192, 1100, 4, seed=21)
    pl = DeltaPlanner(part, g.edge_index, w)
    pl.plan()
    calls = {"n": 0}
    orig = S.locality_block_order

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(S, "locality_block_order", counting)
    ei = pl.edge_index()
    for i in range(4):                    # delete-only: non-structural
        rep = pl.apply(GraphDelta(edge_deletes=ei[:, [i]]), measure_drift=True)
        assert not rep["pads_grown"]
        assert rep["drift"] is not None
    assert calls["n"] == 1, "fresh-reorder BFS must be memoized per era"
    # structural apply (pad growth) bumps the era → exactly one more call
    a = pl.part.assignment
    src = np.flatnonzero(a == 0).astype(np.int64)
    dst = np.full(src.size, int(np.flatnonzero(a == 1)[0]), np.int64)
    rep = pl.apply(GraphDelta(edge_inserts=np.stack([src, dst])),
                   measure_drift=True)
    assert rep["pads_grown"]
    assert calls["n"] == 2
    pl.apply(GraphDelta(edge_deletes=np.stack([src, dst])[:, :1]),
             measure_drift=True)
    assert calls["n"] == 2
    # relocalize seeds the memo with its own reorder: one call, then free
    pl.relocalize()
    n_after = calls["n"]
    pl.apply(GraphDelta(edge_deletes=pl.edge_index()[:, :1]),
             measure_drift=True)
    assert calls["n"] == n_after, "relocalize must seed the drift memo"


# ------------------------------------------------------ live-state carry
def test_relocate_state_tree_round_trip_exact():
    g, w, part = _mk(300, 1800, 4, seed=13)
    pl = DeltaPlanner(part, g.edge_index, w)
    pl.plan()
    rng = np.random.default_rng(4)
    x = rng.standard_normal((pl.n, 8)).astype(np.float32)
    old = plan_layout(pl)
    tree = {
        "m": np.asarray(O.relocate(old, x)),          # per-node moment
        "v": np.asarray(O.relocate(old, x * 2.0)),
        "dense": np.full((3, 3), 7.0, np.float32),    # not per-node: untouched
        "none": None,
    }
    _churn(pl, rng, rounds=4)
    pl.relocalize(block=64)
    new = plan_layout(pl)
    moved = relocate_state_tree(old, new, tree)
    from repro.dist.halo import restore_node_array
    np.testing.assert_array_equal(restore_node_array(new, moved["m"]), x)
    np.testing.assert_array_equal(restore_node_array(new, moved["v"]), x * 2.0)
    assert moved["dense"] is tree["dense"] and moved["none"] is None


def test_relocalize_metrics_and_span_recorded():
    from repro.obs import metrics, trace
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import TraceRecorder

    rec = TraceRecorder()
    old_reg = metrics.set_default_registry(MetricsRegistry())
    old_tracer = trace.set_default_tracer(rec)
    metrics.enable()
    try:
        g, w, part = _mk(192, 1100, 4, seed=15)
        pl = DeltaPlanner(part, g.edge_index, w)
        _churn(pl, np.random.default_rng(6), rounds=3)
        pl.relocalize(block=64)
        pl.compact()
        snap = metrics.snapshot()
        assert snap["delta.relocalizes"]["value"] == 1.0
        assert snap["delta.relocalize_ms"]["count"] == 1
        assert snap["delta.compacts"]["value"] == 1.0
        assert 0.0 < snap["delta.pad_occupancy"]["value"] <= 1.0
        names = {ev.get("name") for ev in rec._events}
        assert "delta.relocalize" in names
    finally:
        metrics.disable()
        metrics.set_default_registry(old_reg)
        trace.set_default_tracer(old_tracer)


# ----------------------------------------------- serve engine across reorder
def test_serve_cache_on_equals_off_across_relocalization():
    """Serve-engine variant of the mid-training acceptance: logits from a
    cached, partition-packed engine must match a fresh cache-less engine
    across {churn deltas → policy fire → adopt_partition} — the resident
    cache and the partition swap may change COST only, never values."""
    import jax
    from repro.core.partition import partition_graph
    from repro.models.gcn import GCNConfig, gcn_init
    from repro.serve.graph import GraphBatcher, hot_query_stream

    g = citation_like(300, 2400, 16, 4, seed=0)
    cfg = GCNConfig(layer_dims=(16, 8, 4))
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    part = partition_graph(g.n_nodes, g.edge_index, 4, method="bfs",
                           seed=0, refine=True)
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=4,
                       cache_capacity=64, partition=part, seed=0)
    pol = RelocalizePolicy(threshold=0.5, patience=1, cooldown=0, block=32)
    pl = DeltaPlanner(part, g.edge_index, graph_key="serve-reloc",
                      relocalize_policy=pol)
    nodes = hot_query_stream(g, 40)
    for _ in range(2):                               # warm the cache
        for v in nodes:
            eng.submit(int(v))
        eng.run_until_drained()
    rng = np.random.default_rng(7)
    fired = 0
    for _ in range(3):
        ei = pl.edge_index()
        drop = rng.choice(ei.shape[1], 20, replace=False)
        mem = rng.choice(g.n_nodes, 16, replace=False)
        s = mem[rng.integers(0, mem.size, 20)]
        d = mem[rng.integers(0, mem.size, 20)]
        bad = s == d
        d[bad] = mem[(np.searchsorted(np.sort(mem), d[bad]) + 1) % mem.size]
        delta = GraphDelta(edge_inserts=np.stack([s, d]),
                           edge_deletes=ei[:, drop])
        eng.apply_graph_delta(delta)
        rep = pl.apply(delta)
        if rep["relocalized"] is not None:
            fired += 1
            eng.adopt_partition(pl.part)
    assert fired >= 1, "relocalization never fired in the serve churn"
    got, want = {}, {}
    oracle = GraphBatcher(params, eng.graph, cfg, batch_seeds=4, fanout=4,
                          cache_capacity=0, seed=0)
    for e, out in ((eng, got), (oracle, want)):
        start = len(e.finished)
        for v in nodes:
            e.submit(int(v))
        e.run_until_drained()
        done = e.finished[start:]
        base = min(q.qid for q in done)
        out.update({q.qid - base: q.logits for q in done})
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5)
    assert eng.cache.hits > 0, "churn run never exercised the cache"


# ------------------------------------------------ 8-device mid-training run
@pytest.mark.slow
def test_relocalize_mid_training_8dev_subprocess():
    """8-device acceptance: a mutation burst crosses the drift threshold
    mid-run; the maintained planner's loss trajectory and final logits match
    the no-maintenance twin to <1e-4, executed tiles drop at the trigger,
    live blocked state rides `relocate_state_tree` bit-exactly, and the
    sharded forward through the re-localized plan still matches the global
    reference."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init
from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.dist.delta import RelocalizePolicy
from repro.dist.halo import plan_layout
from repro.train.elastic import relocate_state_tree

cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow="feature_first")
params = gcn_init(jax.random.PRNGKey(0), cfg)
w = w_of(ei)
A = DeltaPlanner(part, ei, w, graph_key="maint",
                 relocalize_policy=RelocalizePolicy(
                     threshold=1.02, patience=2, cooldown=4, block=32))
B = DeltaPlanner(part, ei, w, graph_key="plain")
planA = A.plan(); B.plan()
labels = np.random.default_rng(2).integers(0, 7, g.n_nodes)
onehot = jnp.asarray(np.eye(7, dtype=np.float32)[labels])

def loss_logits(pl):
    e = pl.edge_index(); ww = pl.edge_weights()
    logits = gcn_forward(params, jnp.asarray(x), jnp.asarray(e[0]),
                         jnp.asarray(e[1]), jnp.asarray(ww), cfg, NO_POLICY)
    return float(-jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), 1))), logits

state = {"m": relocate_node_array(plan_layout(A), x.copy())}
rng = np.random.default_rng(7)
fired = 0
diffs = []
for step in range(30):
    cur = A.edge_index()
    mem = rng.choice(g.n_nodes, 20, replace=False)
    inc = np.flatnonzero(np.isin(cur[0], mem) | np.isin(cur[1], mem))[:24]
    if inc.size == 0:
        continue
    s = mem[rng.integers(0, mem.size, inc.size)]
    d = mem[rng.integers(0, mem.size, inc.size)]
    bad = s == d
    d[bad] = mem[(np.searchsorted(np.sort(mem), d[bad]) + 1) % mem.size]
    ins = np.stack([s, d])
    delta = GraphDelta(edge_inserts=ins, edge_deletes=cur[:, inc],
                       insert_w=w_of(ins))
    repA = A.apply(delta); B.apply(delta)
    r = repA["relocalized"]
    if r is not None:
        fired += 1
        assert r["executed_tiles_after"] < r["executed_tiles_before"], r
        state = relocate_state_tree(r["old_layout"], plan_layout(A), state)
    la, _ = loss_logits(A)
    lb, _ = loss_logits(B)
    diffs.append(abs(la - lb))
assert fired >= 1, "drift never crossed the threshold"
assert max(diffs) < 1e-4, ("loss trajectories diverged", max(diffs))
_, logitsA = loss_logits(A)
_, logitsB = loss_logits(B)
assert np.abs(np.asarray(logitsA) - np.asarray(logitsB)).max() < 1e-4
assert np.array_equal(restore_node_array(plan_layout(A), state["m"]), x), (
    "live state lost bits across relocate_state_tree")

# the maintained (re-localized) plan still serves the sharded forward
mesh1d = make_mesh((8,), ("model",))
xb = jnp.asarray(relocate_node_array(planA, x))
si, sl, rl, ew = planA.device_arrays()
pol0 = ShardingPolicy(comm="halo")
f = jax.shard_map(
    lambda fe, a, b, c, d: gcn_forward(params, fe[0], b[0], c[0], d[0], cfg,
                                       pol0.bind_halo(a[0]))[None],
    mesh=mesh1d, in_specs=(P("model"),) * 5, out_specs=P("model"),
    check_vma=False,
)
got = restore_node_array(planA, np.asarray(f(xb, si, sl, rl, ew)))
e2 = A.edge_index()
ref = np.asarray(gcn_forward(params, jnp.asarray(x), jnp.asarray(e2[0]),
                             jnp.asarray(e2[1]), jnp.asarray(A.edge_weights()),
                             cfg, NO_POLICY))
assert np.abs(got - ref).max() < 1e-4, np.abs(got - ref).max()
print("OK")
"""
    _run(code)
