"""repro.dist coverage beyond the seed suite: plan round-trips, degenerate
partitions, padding hygiene, and the collective path on a 1-device mesh (so
`halo_exchange` is exercised without --xla_force_host_platform_device_count).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core.partition import partition_graph
from repro.dist.halo import build_halo_plan, halo_aggregate, halo_exchange
from repro.graph.generators import citation_like
from repro.graph.ops import aggregate
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------ plan properties
@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(32, 300),
    e=st.integers(50, 1500),
    k=st.sampled_from([1, 2, 4, 8]),
    method=st.sampled_from(["block", "random", "bfs"]),
    seed=st.integers(0, 30),
)
def test_halo_plan_perm_roundtrip(n, e, k, seed, method):
    """Scattering device blocks back through perm restores global order."""
    g = citation_like(n, e, seed=seed)
    part = partition_graph(n, g.edge_index, k, method=method, seed=seed)
    plan = build_halo_plan(part, g.edge_index)
    # perm is a bijection and its inverse undoes it.
    inv = np.empty(n, np.int64)
    inv[plan.perm] = np.arange(n)
    assert np.array_equal(plan.perm[inv], np.arange(n))
    # Block b of the permuted order holds exactly the nodes assigned to b.
    off = 0
    for b in range(k):
        sz = int(part.part_sizes[b])
        assert np.all(part.assignment[plan.perm[off:off + sz]] == b)
        off += sz
    # Relocalization is consistent: mapping every (sender→receiver) pair back
    # to global ids recovers the original edge multiset.
    local_ids = np.full((k, plan.n_local + k * plan.s_max), -1, np.int64)
    sizes = part.part_sizes
    offs = np.concatenate([[0], np.cumsum(sizes)])
    for b in range(k):
        local_ids[b, : sizes[b]] = plan.perm[offs[b]:offs[b + 1]]
    if plan.s_max:
        for b in range(k):
            for j in range(k):
                # halo slot t of source device j is j's exported local row
                base = plan.n_local + j * plan.s_max
                local_ids[b, base: base + plan.s_max] = local_ids[j, plan.send_idx[j]]
    rebuilt = []
    for b in range(k):
        valid = plan.edge_w[b] > 0
        s_glob = local_ids[b, plan.senders_l[b][valid]]
        d_glob = local_ids[b, plan.receivers_l[b][valid]]
        rebuilt.append(np.stack([s_glob, d_glob]))
    rebuilt = np.concatenate(rebuilt, axis=1)
    orig = np.sort(g.edge_index[0].astype(np.int64) * n + g.edge_index[1])
    got = np.sort(rebuilt[0] * n + rebuilt[1])
    assert np.array_equal(got, orig)


def test_halo_plan_k1_has_no_halo():
    g = citation_like(120, 700, seed=5)
    part = partition_graph(120, g.edge_index, 1, method="block")
    plan = build_halo_plan(part, g.edge_index)
    assert plan.k == 1 and plan.s_max == 0 and plan.n_local == 120
    assert int((plan.edge_w > 0).sum()) == 700
    # All senders are local rows — nothing crosses a device boundary.
    assert plan.senders_l.max() < plan.n_local
    assert np.array_equal(plan.perm, np.arange(120))  # block k=1 is identity


def test_halo_plan_isolated_nodes():
    """Nodes with no edges still get block slots; invariants still hold."""
    n, k = 64, 4
    # Edges only among the first 16 nodes: 48 isolated nodes.
    rng = np.random.default_rng(0)
    ei = rng.integers(0, 16, size=(2, 120)).astype(np.int32)
    part = partition_graph(n, ei, k, method="block")
    plan = build_halo_plan(part, ei)
    assert np.array_equal(np.sort(plan.perm), np.arange(n))
    assert int((plan.edge_w > 0).sum()) == 120
    assert plan.receivers_l.max() < plan.n_local
    assert plan.senders_l.max() < plan.n_local + plan.k * plan.s_max
    # Isolated nodes export nothing and receive nothing beyond padding.
    assert plan.s_max <= 16


def test_halo_plan_padding_is_inert():
    g = citation_like(150, 900, seed=2)
    part = partition_graph(150, g.edge_index, 4, method="bfs", seed=0)
    plan = build_halo_plan(part, g.edge_index)
    pad = plan.k * plan.e_local - 900
    assert pad >= 0
    assert int((plan.edge_w == 0).sum()) == pad
    # Padding rows/indices stay in range so gathers never go out of bounds.
    assert plan.senders_l.min() >= 0 and plan.receivers_l.min() >= 0
    assert plan.send_idx.min() >= 0
    if plan.s_max:
        assert plan.send_idx.max() < plan.n_local


def test_halo_plan_custom_weights_and_zero_weight_edges():
    """Explicit weights ride through; a real zero-weight edge is counted as
    padding by the >0 mask (documented contract) but aggregates identically."""
    g = citation_like(80, 400, seed=9)
    w = np.abs(np.random.default_rng(0).standard_normal(400)).astype(np.float32) + 0.1
    w[17] = 0.0                             # one REAL edge with zero weight
    part = partition_graph(80, g.edge_index, 4, method="bfs", seed=1)
    plan = build_halo_plan(part, g.edge_index, w)
    valid = plan.edge_w > 0
    # The zero-weight edge is indistinguishable from padding under the >0
    # mask — by contract it counts as padding (and aggregates identically,
    # since a 0-weight message contributes nothing).
    assert int(valid.sum()) == 399
    np.testing.assert_allclose(np.sort(plan.edge_w[valid]), np.sort(w[w > 0]), rtol=0)


# --------------------------------------------- collectives on a 1-device mesh
def _one_device_mesh():
    if jax.device_count() < 1:  # pragma: no cover
        pytest.skip("no devices")
    return make_mesh((1,), ("model",))


@pytest.mark.parametrize("via", ["all_gather", "ppermute"])
def test_halo_exchange_identity_one_device(via):
    """On a k=1 mesh the halo block is exactly the exported rows."""
    mesh = _one_device_mesh()
    h = jnp.asarray(np.random.default_rng(0).standard_normal((10, 4)), jnp.float32)
    send_idx = jnp.asarray([7, 0, 3], jnp.int32)
    f = jax.shard_map(
        lambda hh, si: halo_exchange(hh[0], si[0], "model", via=via)[None],
        mesh=mesh, in_specs=(P("model"), P("model")), out_specs=P("model"),
        check_vma=False,
    )
    out = np.asarray(f(h[None], send_idx[None]))[0]
    np.testing.assert_array_equal(out, np.asarray(h)[np.asarray(send_idx)])


@pytest.mark.parametrize("via", ["all_gather", "ppermute"])
def test_halo_aggregate_equals_global_one_device(via):
    """The full collective path (k=1 plan) reproduces the global aggregate."""
    mesh = _one_device_mesh()
    g = citation_like(90, 500, seed=4)
    w = np.abs(np.random.default_rng(1).standard_normal(500)).astype(np.float32)
    part = partition_graph(90, g.edge_index, 1, method="block")
    plan = build_halo_plan(part, g.edge_index, w)
    z = np.random.default_rng(2).standard_normal((90, 8)).astype(np.float32)
    si, sl, rl, ew = plan.device_arrays()
    f = jax.shard_map(
        lambda zz, a, b, c, d: halo_aggregate(zz[0], a[0], b[0], c[0], d[0], "model", via=via)[None],
        mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"),
        check_vma=False,
    )
    out = np.asarray(f(jnp.asarray(z)[None], si, sl, rl, ew))[0]
    ref = np.asarray(aggregate(jnp.asarray(z), jnp.asarray(g.edge_index[0]),
                               jnp.asarray(g.edge_index[1]), 90, jnp.asarray(w)))
    np.testing.assert_allclose(out[plan.perm.argsort()], ref, rtol=1e-5, atol=1e-5)


def test_wire_volume_helpers_match_invariant():
    g = citation_like(2000, 12000, seed=1)
    part = partition_graph(2000, g.edge_index, 8, method="bfs", seed=0, refine=True)
    plan = build_halo_plan(part, g.edge_index)
    assert plan.halo_rows_per_device == plan.k * plan.s_max
    assert plan.broadcast_rows_per_device == (plan.k - 1) * plan.n_local
    assert plan.wire_fraction() < 1.0


# --------------------------------------------------------------- plan cache
def test_plan_cache_same_graph_reuses_object():
    from repro.dist import halo

    halo.invalidate_halo_plans()
    g = citation_like(120, 700, seed=7)
    part = partition_graph(120, g.edge_index, 4, method="bfs", seed=0)
    before = halo.plan_cache_stats()
    p1 = halo.get_halo_plan(part, g.edge_index)
    p2 = halo.get_halo_plan(part, g.edge_index)
    assert p1 is p2                              # same graph/partition/k → same object
    after = halo.plan_cache_stats()
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 1


def test_plan_cache_mutated_graph_or_k_rebuilds():
    from repro.dist import halo

    halo.invalidate_halo_plans()
    g = citation_like(120, 700, seed=7)
    part4 = partition_graph(120, g.edge_index, 4, method="bfs", seed=0)
    p1 = halo.get_halo_plan(part4, g.edge_index)
    # Different k → different cache entry.
    part8 = partition_graph(120, g.edge_index, 8, method="bfs", seed=0)
    p8 = halo.get_halo_plan(part8, g.edge_index)
    assert p8 is not p1 and p8.k == 8
    # Mutated edge list → different fingerprint → rebuild.
    ei2 = g.edge_index.copy()
    ei2[1, 0] = (ei2[1, 0] + 1) % 120
    part_m = partition_graph(120, ei2, 4, method="bfs", seed=0)
    pm = halo.get_halo_plan(part_m, ei2)
    assert pm is not p1
    # Same graph, different partition (seed) → no collision either.
    part_s = partition_graph(120, g.edge_index, 4, method="random", seed=3)
    ps = halo.get_halo_plan(part_s, g.edge_index)
    assert ps is not p1
    assert halo.plan_cache_stats()["size"] >= 4
    evicted = halo.invalidate_halo_plans()
    assert evicted >= 4
    assert halo.get_halo_plan(part4, g.edge_index) is not p1   # rebuilt


def test_plan_cache_lazy_builder_runs_once():
    from repro.dist.halo import cached_halo_plan, invalidate_halo_plans

    invalidate_halo_plans()
    calls = []

    def build():
        calls.append(1)
        g = citation_like(64, 300, seed=1)
        part = partition_graph(64, g.edge_index, 2, method="block")
        from repro.dist.halo import build_halo_plan

        return build_halo_plan(part, g.edge_index)

    p1 = cached_halo_plan("unit:lazy", 2, builder=build)
    p2 = cached_halo_plan("unit:lazy", 2, builder=build)
    assert p1 is p2 and len(calls) == 1
    # Axis is part of the key (hierarchical meshes cache per axis).
    p3 = cached_halo_plan("unit:lazy", 2, "pod", builder=build)
    assert p3 is not p1 and len(calls) == 2


def test_plan_cache_elastic_resize_invalidates():
    from repro.dist import halo
    from repro.train.elastic import elastic_replan

    halo.invalidate_halo_plans()
    g = citation_like(100, 500, seed=2)
    part = partition_graph(100, g.edge_index, 8, method="bfs", seed=0)
    p1 = halo.get_halo_plan(part, g.edge_index)
    # Data-axis-only shrink keeps the model degree → plans stay valid.
    keep = elastic_replan(32, 8)
    assert keep.shape == (4, 8)
    assert halo.get_halo_plan(part, g.edge_index) is p1
    # Model-degree change = re-partition event → full invalidation.
    shrink = elastic_replan(4, 8)
    assert shrink.shape[1] == 4
    assert halo.get_halo_plan(part, g.edge_index) is not p1


def test_relocate_restore_roundtrip_and_node_mask():
    from repro.dist.halo import get_halo_plan, node_mask, relocate_node_array, restore_node_array

    g = citation_like(90, 400, seed=11)
    part = partition_graph(90, g.edge_index, 4, method="bfs", seed=1)
    plan = get_halo_plan(part, g.edge_index)
    x = np.random.default_rng(0).standard_normal((90, 5)).astype(np.float32)
    blocks = relocate_node_array(plan, x)
    assert blocks.shape == (4, plan.n_local, 5)
    np.testing.assert_array_equal(restore_node_array(plan, blocks), x)
    mask = node_mask(plan)
    assert mask.shape == (4, plan.n_local)
    assert int(mask.sum()) == 90
    # Padding rows are zero in the blocked layout.
    assert np.all(blocks[mask == 0] == 0)


# -------------------------------------------------------------------- policy
def test_policy_constrain_noop_and_named():
    from repro.dist.policy import NO_POLICY, ShardingPolicy

    x = jnp.ones((4, 4))
    assert NO_POLICY.constrain(x, "anything") is x
    mesh = make_mesh((1,), ("model",))
    pol = ShardingPolicy(mesh=mesh, specs={"h": P("model", None)})
    assert pol.constrain(x, "unregistered") is x
    y = pol.constrain(x, "h")                      # applies, values unchanged
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert pol.spec("h") == P("model", None)
    assert pol.sharding("h").mesh is not None
    pol2 = pol.with_specs(h=P(None, "model"))
    assert pol2.spec("h") == P(None, "model") and pol.spec("h") == P("model", None)


def test_policy_comm_mode_and_neighbor_table():
    from repro.dist.policy import NO_POLICY, ShardingPolicy

    x = jnp.arange(12.0).reshape(6, 2)
    # Broadcast / NO_POLICY: the table is the identity.
    assert NO_POLICY.neighbor_table(x) is x
    halo_pol = ShardingPolicy(comm="halo")
    # Unbound halo (outside shard_map) is inert too.
    assert not halo_pol.is_halo
    assert halo_pol.neighbor_table(x) is x
    bound = halo_pol.bind_halo(jnp.asarray([0, 3], jnp.int32))
    assert bound.is_halo and not halo_pol.is_halo       # bind returns a copy
    # with_specs preserves the comm mode.
    assert halo_pol.with_specs(h=P("model", None)).comm == "halo"
