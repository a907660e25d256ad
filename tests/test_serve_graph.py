"""repro.serve.graph: online GCN query serving + hot-neighbor cache.

Pins the subsystem's three contracts (ISSUE 3 acceptance):
  * compile-once — ONE trace serves micro-batches of different live sizes,
  * cache-on == cache-off logits (fp32 tolerance) with strictly fewer
    sampled nodes+edges per query,
  * degree-ranked eviction under a tiny capacity, and invalidation on
    weight/feature updates.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _delta_oracle import random_delta
from repro.core.partition import partition_graph
from repro.graph.generators import citation_like
from repro.models.gcn import GCNConfig, gcn_init
from repro.serve.graph import (
    GraphBatcher,
    HotNeighborCache,
    ServeSampler,
    hot_query_stream,
)


def _setup(seed=0, n=300, e=2400, f=16, c=4, hidden=8, dims=None):
    g = citation_like(n, e, f, c, seed=seed)
    cfg = GCNConfig(layer_dims=dims or (f, hidden, c))
    params = gcn_init(jax.random.PRNGKey(seed), cfg)
    return g, cfg, params


# ------------------------------------------------------------------- sampler
def test_serve_sampler_deterministic_and_pure():
    g, _, _ = _setup()
    s1 = ServeSampler(g, fanout=4, n_layers=2, seed=7)
    s2 = ServeSampler(g, fanout=4, n_layers=2, seed=7)
    nodes = np.arange(50)
    np.testing.assert_array_equal(s1.neighbors(nodes), s2.neighbors(nodes))
    # Purity: a node's draw does not depend on which batch it appears in.
    np.testing.assert_array_equal(
        s1.neighbors(np.asarray([3])), s1.neighbors(np.asarray([9, 3, 40]))[1:2]
    )
    # A different seed gives a different sampled graph.
    s3 = ServeSampler(g, fanout=4, n_layers=2, seed=8)
    assert not np.array_equal(s1.neighbors(nodes), s3.neighbors(nodes))


def test_serve_sampler_block_replay_identical():
    g, _, _ = _setup()
    s = ServeSampler(g.with_self_loops(), fanout=3, n_layers=2, seed=0)
    seeds = np.asarray([5, 17, 100])
    a = s.sample_block(seeds, batch_seeds=4)
    b = s.sample_block(seeds, batch_seeds=4)
    np.testing.assert_array_equal(a.node_ids, b.node_ids)
    np.testing.assert_array_equal(a.senders, b.senders)
    np.testing.assert_array_equal(a.receivers, b.receivers)
    np.testing.assert_allclose(a.edge_weight, b.edge_weight)
    # Ghost-padding hygiene: pads are inert (weight 0, ids out of valid range).
    assert np.all(a.node_ids[a.n_nodes:] == -1)
    assert np.all(a.senders[a.n_edges:] == a.max_nodes)
    assert np.all(a.edge_weight[a.n_edges:] == 0.0)
    assert a.senders[: a.n_edges].max() < a.n_nodes


# -------------------------------------------------------------- compile once
def test_compile_once_across_live_sizes():
    g, cfg, params = _setup()
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=3, seed=0)
    for wave in ([1, 2, 3, 4], [5, 6], [7]):       # live sizes 4, 2, 1
        for v in wave:
            eng.submit(v)
        eng.step()
    assert eng.micro_batches == 3
    assert eng.traces == 1, "fixed-shape micro-batches must not retrace"
    assert all(q.logits is not None for q in eng.finished)


# ------------------------------------------------------- cache == no cache
def _serve_two_waves(g, cfg, params, nodes, capacity):
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=4,
                       cache_capacity=capacity, seed=0)
    for wave in (nodes, nodes):                    # second wave replays hot set
        for v in wave:
            eng.submit(int(v))
        eng.run_until_drained()
    return eng


def test_cache_on_matches_cache_off_with_fewer_samples():
    g, cfg, params = _setup()
    nodes = hot_query_stream(g, 40)
    off = _serve_two_waves(g, cfg, params, nodes, capacity=0)
    on = _serve_two_waves(g, cfg, params, nodes, capacity=64)
    lo = {q.qid: q.logits for q in off.finished}
    ln = {q.qid: q.logits for q in on.finished}
    assert set(lo) == set(ln)
    for k in lo:
        np.testing.assert_allclose(ln[k], lo[k], rtol=1e-5, atol=1e-5)
    assert on.cache.hits > 0
    assert (on.nodes_sampled + on.edges_sampled) < (off.nodes_sampled + off.edges_sampled)
    s = on.stats()["cache"]
    assert s["rows_saved"] > 0 and s["bytes_saved"] > 0


def test_cache_exactness_three_layer_gcn():
    """Deep-GCN regression: every edge runs at every layer in the merged
    forward, so requirements must propagate as (node, layer) pairs — a
    truncated hub's non-injected layers must never leak into a read value
    (they did under naive depth-BFS truncation, e.g. via self-loops)."""
    g, cfg, params = _setup(dims=(16, 8, 8, 4))          # 3 layers
    nodes = hot_query_stream(g, 40)
    off = _serve_two_waves(g, cfg, params, nodes, capacity=0)
    on = _serve_two_waves(g, cfg, params, nodes, capacity=64)
    assert on.cache.hits > 0
    lo = {q.qid: q.logits for q in off.finished}
    for q in on.finished:
        np.testing.assert_allclose(q.logits, lo[q.qid], rtol=1e-5, atol=1e-5)
    assert (on.nodes_sampled + on.edges_sampled) < (off.nodes_sampled + off.edges_sampled)


def test_eviction_under_tiny_capacity():
    g, cfg, params = _setup()
    nodes = hot_query_stream(g, 48)
    on = _serve_two_waves(g, cfg, params, nodes, capacity=2)
    assert len(on.cache) <= 2
    assert on.cache.evictions > 0
    # Correctness must survive eviction churn.
    off = _serve_two_waves(g, cfg, params, nodes, capacity=0)
    for qo, qn in zip(off.finished, on.finished):
        np.testing.assert_allclose(qn.logits, qo.logits, rtol=1e-5, atol=1e-5)


def test_cache_hits_counted_exactly_once_hand_counted():
    """Regression (ISSUE 6 satellite 2): ``stats()["hits"]`` counts each
    serving hit EXACTLY once — at lookup time during sampling. The old
    harvest path re-added ``blk.cache_hits`` on top, doubling hits and
    inflating hit_rate. Hand-counted: sample a block against an empty cache,
    admit the frontier's layer-1 rows, resample — every lookup tally on the
    cache must equal the block's own per-sample counts."""
    g, cfg, _ = _setup()
    s = ServeSampler(g, fanout=3, n_layers=2, seed=0)
    c = HotNeighborCache(capacity=64, degree=s.in_deg)
    seeds = np.asarray([5, 17])
    blk = s.sample_block(seeds, batch_seeds=2, cache=c)
    # cold cache: every lookup misses, counted once each, zero hits
    assert blk.cache_hits == 0 and c.hits == 0
    assert blk.cache_misses > 0 and c.misses == blk.cache_misses
    # Warm every block node's layer-1 row (a superset of what was looked
    # up — extra entries are inert, only actual lookups count), resample:
    # the same layer-1 lookups now hit, once per lookup, nothing re-added
    # on any other path.
    for v in blk.node_ids[: blk.n_nodes]:
        c.admit(int(v), 1, np.ones(cfg.layer_dims[1], np.float32))
    h0, m0 = c.hits, c.misses
    blk2 = s.sample_block(seeds, batch_seeds=2, cache=c)
    assert blk2.cache_hits > 0
    assert c.hits - h0 == blk2.cache_hits          # exactly once per hit
    assert c.misses - m0 == blk2.cache_misses
    assert c.stats()["hits"] == c.hits
    assert c.stats()["hit_rate"] == pytest.approx(
        c.hits / (c.hits + c.misses)
    )


def test_engine_hits_match_lookup_tally():
    """End-to-end double-count guard: wrap ``cache.lookup`` to count calls
    independently; after serving two waves the engine's ``stats()`` hit/miss
    totals must equal the wrapper's tally (the old harvest re-add made
    ``hits`` exactly double the true count)."""
    g, cfg, params = _setup()
    nodes = hot_query_stream(g, 40)
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=4,
                       cache_capacity=64, seed=0)
    calls = {"hit": 0, "miss": 0}
    orig_lookup = eng.cache.lookup

    def counting_lookup(node, layer):
        val = orig_lookup(node, layer)
        calls["hit" if val is not None else "miss"] += 1
        return val

    eng.cache.lookup = counting_lookup
    for wave in (nodes, nodes):
        for v in wave:
            eng.submit(int(v))
        eng.run_until_drained()
    s = eng.stats()["cache"]
    assert calls["hit"] > 0
    assert s["hits"] == calls["hit"]
    assert s["misses"] == calls["miss"]


def test_bytes_saved_dtype_aware_formula():
    """bytes_saved derives from the feature array's dtype itemsize and the
    injected row's actual nbytes (not a hard-coded 4·F with no injection
    credit): each layer-1 injection saves rows·F·itemsize gathered feature
    bytes minus the H·itemsize activation row shipped in their place."""
    g, cfg, params = _setup()                       # F=16, H=8, 2 layers
    nodes = hot_query_stream(g, 32)
    on = _serve_two_waves(g, cfg, params, nodes, capacity=64)
    s = on.stats()["cache"]
    assert s["rows_saved"] > 0
    feat_bytes = on.features.dtype.itemsize * on.features.shape[1]
    row_bytes = on.features.dtype.itemsize * cfg.layer_dims[1]
    rows_per = on.sampler.subtree_counts(1)[0]      # per-injection row credit
    assert s["rows_saved"] % rows_per == 0
    n_inj = s["rows_saved"] // rows_per
    assert s["bytes_saved"] == pytest.approx(
        s["rows_saved"] * feat_bytes - n_inj * row_bytes
    )
    # the injected activation row is a real cost — never free bandwidth
    assert s["bytes_saved"] < s["rows_saved"] * feat_bytes


def test_degree_ranked_admission():
    deg = np.asarray([10, 1, 5, 7])
    c = HotNeighborCache(capacity=2, degree=deg)
    v = np.ones(4, np.float32)
    assert c.admit(1, 1, v)            # deg 1
    assert c.admit(2, 1, v)            # deg 5 → full
    assert c.admit(0, 1, v)            # deg 10 evicts deg 1
    assert c.lookup(1, 1) is None and c.lookup(0, 1) is not None
    assert not c.admit(1, 1, v)        # deg 1 cannot evict deg 5
    assert c.evictions == 1


# ------------------------------------------------------------- invalidation
def test_cache_invalidated_on_weight_and_feature_update():
    g, cfg, params = _setup()
    nodes = hot_query_stream(g, 24)
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=4,
                       cache_capacity=64, seed=0)
    for v in nodes:
        eng.submit(int(v))
    eng.run_until_drained()
    assert len(eng.cache) > 0
    new_params = gcn_init(jax.random.PRNGKey(99), cfg)
    eng.update_params(new_params)
    assert len(eng.cache) == 0 and eng.cache.invalidations == 1
    # Post-update logits must match a fresh engine on the new weights (no
    # stale activation may leak through the cache).
    for v in nodes:
        eng.submit(int(v))
    eng.run_until_drained()
    ref = GraphBatcher(new_params, g, cfg, batch_seeds=4, fanout=4, seed=0)
    for v in nodes:
        ref.submit(int(v))
    ref.run_until_drained()
    for qa, qb in zip(eng.finished[len(nodes):], ref.finished):
        np.testing.assert_allclose(qa.logits, qb.logits, rtol=1e-5, atol=1e-5)
    eng.update_features(np.asarray(g.features))
    assert eng.cache.invalidations == 2


# ------------------------------------------------------- partition packing
def test_partition_aligned_packing_groups_parts():
    g, cfg, params = _setup()
    part = partition_graph(g.n_nodes, g.edge_index, 2, method="block")
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=3,
                       partition=part, seed=0)
    # Interleave queries from the two halves; packing should un-interleave.
    lo, hi = [1, 2, 3, 4], [290, 291, 292, 293]
    for a, b in zip(lo, hi):
        eng.submit(a)
        eng.submit(b)
    first = eng.step()
    second = eng.step()
    p_first = {int(part.assignment[q.node]) for q in first}
    p_second = {int(part.assignment[q.node]) for q in second}
    assert len(p_first) == 1 and len(p_second) == 1 and p_first != p_second


# ------------------------------------------------------------ other models
def test_pna_and_egnn_serve_smoke():
    from repro.models.egnn import EGNNConfig, egnn_init
    from repro.models.pna import PNAConfig, pna_init

    g = citation_like(120, 900, 8, 3, seed=0, with_positions=True)
    pcfg = PNAConfig(n_layers=2, d_hidden=12, d_in=8, d_out=3)
    eng = GraphBatcher(pna_init(jax.random.PRNGKey(0), pcfg), g, pcfg,
                       model="pna", batch_seeds=3, fanout=3, seed=0)
    for v in (4, 9, 40, 80):
        eng.submit(v)
    eng.run_until_drained()
    assert eng.traces == 1 and all(np.isfinite(q.logits).all() for q in eng.finished)

    ecfg = EGNNConfig(n_layers=2, d_hidden=12, d_in=8, d_out=2)
    eng = GraphBatcher(egnn_init(jax.random.PRNGKey(0), ecfg), g, ecfg,
                       model="egnn", batch_seeds=3, fanout=3, seed=0)
    for v in (4, 9, 40):
        eng.submit(v)
    eng.run_until_drained()
    assert eng.traces == 1 and all(np.isfinite(q.logits).all() for q in eng.finished)

    with pytest.raises(ValueError):
        GraphBatcher(pna_init(jax.random.PRNGKey(0), pcfg), g, pcfg,
                     model="pna", cache_capacity=8)


# ------------------------------------------------------- mutating the graph
def _fresh_oracle(eng):
    """A cache-less engine rebuilt on ``eng``'s CURRENT graph — the no-cache
    ground truth for whatever mutations ``eng`` has absorbed in place."""
    return GraphBatcher(eng.params, eng.graph, eng.cfg,
                        batch_seeds=eng.batch_seeds, fanout=eng.sampler.fanout,
                        cache_capacity=0, seed=eng._seed)


def _serve_wave(eng, nodes):
    start = len(eng.finished)
    for v in nodes:
        eng.submit(int(v))
    eng.run_until_drained()
    done = eng.finished[start:]
    base = min(q.qid for q in done)
    return {q.qid - base: q.logits for q in done}


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 40))
def test_interleaved_mutations_match_no_cache_oracle(seed, pytestconfig):
    """Property: under ANY interleaving of {serve wave, GraphDelta,
    scoped feature update} the cached engine's logits match a fresh
    cache-less engine rebuilt on the current graph — i.e. the scoped
    frontier-walk invalidation never leaves a stale activation behind.

    Reads ``--delta-seed`` through the session-scoped ``pytestconfig``:
    hypothesis refuses function-scoped fixtures under ``@given``."""
    delta_seed = int(pytestconfig.getoption("--delta-seed"))
    rng = np.random.default_rng((seed << 10) ^ delta_seed)
    g, cfg, params = _setup(seed=seed % 5, n=40, e=160, f=8, hidden=6)
    eng = GraphBatcher(params, g, cfg, batch_seeds=4, fanout=2,
                       cache_capacity=16, seed=0)
    f_dim = g.features.shape[1]
    for _ in range(8):
        op = rng.random()
        if op < 0.30:
            d = random_delta(rng, g.n_nodes, eng.graph.edge_index,
                             max_ops=6, feat_dim=f_dim)
            rep = eng.apply_graph_delta(d)
            assert rep["residents_dropped"] <= rep["residents_before"]
        elif op < 0.45:
            touched = np.unique(rng.integers(0, g.n_nodes, 3))
            feats = np.array(eng.features)
            feats[touched] += rng.standard_normal(
                (touched.size, f_dim)).astype(np.float32)
            eng.update_features(feats, touched=touched)
        # hot skew (nodes 0..15) so replays actually hit the cache
        wave = rng.integers(0, 16, 4)
        got = _serve_wave(eng, wave)
        want = _serve_wave(_fresh_oracle(eng), wave)
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5)
    assert eng.cache.hits > 0, "interleaving never exercised the cache"


def test_scoped_invalidation_drops_strictly_fewer_than_all():
    """A localized delta (one low-degree edge deleted) must NOT nuke the
    cache: only residents whose sampled cone reaches the endpoints drop,
    the survivors keep serving, and post-delta logits stay exact."""
    g, cfg, params = _setup()
    nodes = hot_query_stream(g, 40)
    eng = _serve_two_waves(g, cfg, params, nodes, capacity=64)
    resident = len(eng.cache)
    assert resident > 8, "need a warm cache for the scoped-drop contract"
    deg = eng.sampler.in_deg
    ei = eng.graph.edge_index
    quiet = int(np.argmin(deg[ei[0]] + deg[ei[1]]))
    from repro.dist.delta import GraphDelta
    rep = eng.apply_graph_delta(GraphDelta(edge_deletes=ei[:, [quiet]]))
    assert rep["residents_before"] == resident
    assert rep["residents_dropped"] < resident, (
        "scoped invalidation degenerated into a full flush")
    assert len(eng.cache) == resident - rep["residents_dropped"]
    assert eng.cache.scoped_invalidations == 1
    assert eng.cache.invalidations == 0, "must not take the full-flush path"
    got = _serve_wave(eng, nodes)
    want = _serve_wave(_fresh_oracle(eng), nodes)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5)
