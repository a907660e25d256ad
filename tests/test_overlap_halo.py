"""Overlapped halo schedule + quantized wire payloads (docs/communication.md
"Overlapped schedule"): interior/boundary row-partition invariants, numpy
emulation of the split aggregation, split blocked-adjacency equivalence,
plan-cache eviction accounting, and the 8-device overlapped-vs-serialized /
payload-tolerance subprocess acceptance runs.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.partition import partition_graph
from repro.dist.halo import build_halo_plan
from repro.graph.generators import citation_like

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _blocked(plan, x: np.ndarray) -> np.ndarray:
    out = np.zeros((plan.k, plan.n_local) + x.shape[1:], x.dtype)
    off = 0
    for b in range(plan.k):
        sz = int(plan.part_sizes[b])
        out[b, :sz] = x[plan.perm[off:off + sz]]
        off += sz
    return out


def _flat_halo(plan, zb: np.ndarray) -> np.ndarray:
    """Pure-numpy emulation of the flat halo block (the all-gather of every
    member's export rows — identical on all devices)."""
    return np.concatenate([zb[m][plan.send_idx[m]] for m in range(plan.k)], axis=0)


# ---------------------------------------------------- interior/boundary split
@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(64, 400),
    e=st.integers(100, 2000),
    k=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 50),
)
def test_interior_boundary_partition_every_row_exactly_once(n, e, k, seed):
    """The tentpole invariant: interior ∪ boundary covers every block row of
    every device exactly once (padding rows count interior), and the edge
    split is exhaustive — interior + boundary == every real edge."""
    g = citation_like(n, e, seed=seed)
    part = partition_graph(n, g.edge_index, k, method="bfs", seed=seed)
    plans = [build_halo_plan(part, g.edge_index)]
    if k >= 4:
        plans.append(build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2))
    for plan in plans:
        bm, im = plan.boundary_row_mask(), plan.interior_row_mask()
        assert bm.shape == im.shape == (plan.k, plan.n_local)
        # partition: every row in exactly one set
        assert np.array_equal(bm ^ im, np.ones_like(bm))
        assert int(plan.interior_edges) + int(plan.boundary_edges) == e
        assert 0.0 <= plan.overlap_fraction() <= 1.0
        assert int(plan.boundary_rows_per_device().sum()
                   + plan.interior_rows_per_device().sum()) == plan.k * plan.n_local
        # boundary rows receive ≥1 halo edge each, so they can't outnumber them
        assert int(plan.boundary_rows_per_device().sum()) <= int(plan.boundary_edges)


def test_overlap_fraction_extremes():
    """k=1 has no halo senders at all → everything interior, fraction 1."""
    g = citation_like(100, 600, seed=3)
    part = partition_graph(100, g.edge_index, 1, method="block")
    plan = build_halo_plan(part, g.edge_index)
    assert plan.boundary_edges == 0 and plan.overlap_fraction() == 1.0
    assert not plan.boundary_row_mask().any()


def test_split_aggregate_matches_combined_numpy_emulation():
    """split_halo_aggregate(z, halo) == the combined [local ‖ halo] gather
    aggregation, bit-for-bit on the same table rows (flat 4-way plan)."""
    import jax.numpy as jnp

    from repro.dist.halo import split_halo_aggregate

    g = citation_like(300, 1800, seed=9)
    w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
    part = partition_graph(g.n_nodes, g.edge_index, 4, method="bfs", seed=0, refine=True)
    plan = build_halo_plan(part, g.edge_index, w)
    z = np.random.default_rng(1).standard_normal((g.n_nodes, 12)).astype(np.float32)
    zb = _blocked(plan, z)
    halo = _flat_halo(plan, zb)
    for dev in range(plan.k):
        table = np.concatenate([zb[dev], halo], axis=0)
        ref = np.zeros_like(zb[dev])
        np.add.at(ref, plan.receivers_l[dev],
                  table[plan.senders_l[dev]] * plan.edge_w[dev][:, None])
        out = np.asarray(split_halo_aggregate(
            jnp.asarray(zb[dev]), jnp.asarray(halo),
            jnp.asarray(plan.senders_l[dev]), jnp.asarray(plan.receivers_l[dev]),
            jnp.asarray(plan.edge_w[dev]),
        ))
        np.testing.assert_allclose(out, ref, atol=2e-5)


def test_split_blocked_adjacency_matches_combined():
    """interior(z) + boundary(halo) through the split bsr tables equals the
    combined per-shard blocked aggregation — per device, both plans cached."""
    import jax.numpy as jnp

    from repro.dist.halo import (
        plan_blocked_adjacency,
        plan_split_blocked_adjacency,
        plan_split_blocked_shape,
    )
    from repro.kernels.ops import bsr_spmm

    g = citation_like(300, 1800, seed=9)
    w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
    part = partition_graph(g.n_nodes, g.edge_index, 4, method="bfs", seed=0, refine=True)
    plan = build_halo_plan(part, g.edge_index, w)
    comb = plan_blocked_adjacency(plan)
    ia, bd = plan_split_blocked_adjacency(plan)
    assert plan_split_blocked_adjacency(plan) == (ia, bd)   # memoized
    shp = plan_split_blocked_shape(plan)
    assert shp["interior"]["nnz_blocks"] == ia.nnz_blocks
    assert shp["boundary"]["nnz_blocks"] == bd.nnz_blocks
    assert shp["overlap_fraction"] == plan.overlap_fraction()
    z = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
    zb = _blocked(plan, z)
    halo = _flat_halo(plan, zb)
    cv, cc, cl = comb.device_arrays()
    iv, ic, il = ia.device_arrays()
    bv, bc, bl = bd.device_arrays()
    for dev in range(plan.k):
        table = jnp.asarray(np.concatenate([zb[dev], halo], axis=0))
        ref = np.asarray(bsr_spmm(cv[dev], cc[dev], table, lens=cl[dev]))[: plan.n_local]
        interior = bsr_spmm(iv[dev], ic[dev], jnp.asarray(zb[dev]), lens=il[dev])
        boundary = bsr_spmm(bv[dev], bc[dev], jnp.asarray(halo), lens=bl[dev])
        out = np.asarray(interior)[: plan.n_local] + np.asarray(boundary)[: plan.n_local]
        np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


# ------------------------------------------------------- plan-cache evictions
def test_plan_cache_evictions_counted_and_resettable():
    """Satellite 3: `invalidate_halo_plans` bumps the `evictions` counter by
    the number of entries dropped, and `reset_plan_cache_stats` zeroes the
    counters WITHOUT touching cached entries."""
    from repro.dist import halo

    halo.invalidate_halo_plans()
    halo.reset_plan_cache_stats()
    g = citation_like(120, 700, seed=11)
    part = partition_graph(120, g.edge_index, 4, method="bfs", seed=0)
    plan = halo.get_halo_plan(part, g.edge_index)                 # miss
    assert halo.get_halo_plan(part, g.edge_index) is plan         # hit
    s = halo.plan_cache_stats()
    assert s["misses"] == 1 and s["hits"] == 1 and s["evictions"] == 0
    # reset leaves the entry hot: the next get is a HIT on the same object.
    halo.reset_plan_cache_stats()
    s = halo.plan_cache_stats()
    assert s["hits"] == s["misses"] == s["evictions"] == 0 and s["size"] >= 1
    assert halo.get_halo_plan(part, g.edge_index) is plan
    assert halo.plan_cache_stats()["hits"] == 1
    # targeted invalidation counts exactly the dropped entries
    key = halo.graph_fingerprint(part.n_nodes, g.edge_index, None, part.assignment)
    dropped = halo.invalidate_halo_plans(key)
    assert dropped >= 1
    assert halo.plan_cache_stats()["evictions"] == dropped
    # full invalidation keeps accumulating
    halo.get_halo_plan(part, g.edge_index)
    dropped2 = halo.invalidate_halo_plans()
    assert halo.plan_cache_stats()["evictions"] == dropped + dropped2


# --------------------------------------------------- 8-device acceptance runs
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
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_graph
from repro.dist.halo import build_halo_plan, get_halo_plan, relocate_node_array, restore_node_array
from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.graph.generators import citation_like
from repro.launch.mesh import make_mesh

g = citation_like(400, 2400, seed=5)
w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
# Receiver-degree normalization (the GCN Ã convention): row sums of 1 keep
# the aggregation non-amplifying, so wire rounding stays O(eps·|act|) per hop
# instead of growing with the weighted degree.
_deg = np.bincount(g.edge_index[1], weights=w, minlength=g.n_nodes)
w = (w / _deg[g.edge_index[1]]).astype(np.float32)
part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
senders = jnp.asarray(g.edge_index[0]); receivers = jnp.asarray(g.edge_index[1])
"""


@pytest.mark.slow
def test_gcn_overlapped_equals_serialized_flat_subprocess():
    """The tentpole acceptance, flat 8-way: the overlapped (split
    interior/boundary) schedule equals both the serialized halo schedule and
    the global forward, for BOTH dataflow orders, and bf16/int8 payloads stay
    within their documented tolerances."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

plan = get_halo_plan(part, g.edge_index, w)
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
xb = jnp.asarray(relocate_node_array(plan, x))

def run(pol0, cfg, params):
    def body(fe, a, b, c, d):
        return gcn_forward(params, fe, b, c, d, cfg, pol0.bind_halo(a))
    f = jax.shard_map(
        lambda fe, a, b, c, d: body(fe[0], a[0], b[0], c[0], d[0])[None],
        mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
    )
    return restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew)))

for dataflow in ("feature_first", "aggregation_first"):
    cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow=dataflow)
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(gcn_forward(params, jnp.asarray(x), senders, receivers,
                                 jnp.asarray(w), cfg, NO_POLICY))
    overlapped = run(ShardingPolicy(comm="halo", halo_overlap=True), cfg, params)
    serialized = run(ShardingPolicy(comm="halo", halo_overlap=False), cfg, params)
    assert np.abs(serialized - ref).max() < 1e-4, dataflow
    assert np.abs(overlapped - ref).max() < 1e-4, dataflow
    # quantized wire payloads, overlapped schedule
    bf16 = run(ShardingPolicy(comm="halo", halo_payload="bf16"), cfg, params)
    assert np.abs(bf16 - ref).max() < 1e-2, (dataflow, np.abs(bf16 - ref).max())
    int8 = run(ShardingPolicy(comm="halo", halo_payload="int8"), cfg, params)
    # int8 documented tolerance (docs/communication.md): per-export-block
    # amax/254 wire rounding through two quantized halo hops, the second on
    # post-matmul activations — measured ~0.026 max-abs here, so 5e-2 abs
    # plus a 1% relative-L2 guard against gross breakage.
    err8 = np.abs(int8 - ref).max()
    rel8 = np.linalg.norm(int8 - ref) / np.linalg.norm(ref)
    assert err8 < 5e-2 and rel8 < 1e-2, (dataflow, err8, rel8)
print("OK")
"""
    _run(code)


@pytest.mark.slow
def test_gcn_overlapped_equals_serialized_hier_subprocess():
    """Same acceptance on the hierarchical 2×4 (pod, model) mesh — the
    two-phase exchange under the overlapped schedule and bf16 payload."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

plan = build_halo_plan(part, g.edge_index, w, axes=("pod", "model"), pods=2)
mesh = make_mesh((2, 4), ("pod", "model"))
sloc, srem, sl, rl, ew = plan.device_arrays()
xb = jnp.asarray(relocate_node_array(plan, x))

def run(pol0, cfg, params):
    def body(fe, a, a2, b, c, d):
        pol = pol0.bind_halo(send_loc=a[0], send_rem=a2[0])
        return gcn_forward(params, fe[0], b[0], c[0], d[0], cfg, pol)[None]
    f = jax.shard_map(body, mesh=mesh, in_specs=(P(("pod", "model")),) * 6,
                      out_specs=P(("pod", "model")), check_vma=False)
    return restore_node_array(plan, np.asarray(f(xb, sloc, srem, sl, rl, ew)))

base = ShardingPolicy(comm="halo", halo_axes=("pod", "model"))
for dataflow in ("feature_first", "aggregation_first"):
    cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow=dataflow)
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(gcn_forward(params, jnp.asarray(x), senders, receivers,
                                 jnp.asarray(w), cfg, NO_POLICY))
    overlapped = run(base, cfg, params)
    serialized = run(dataclasses.replace(base, halo_overlap=False), cfg, params)
    assert np.abs(serialized - ref).max() < 1e-4, dataflow
    assert np.abs(overlapped - ref).max() < 1e-4, dataflow
    bf16 = run(dataclasses.replace(base, halo_payload="bf16"), cfg, params)
    assert np.abs(bf16 - ref).max() < 1e-2, (dataflow, np.abs(bf16 - ref).max())
print("OK")
"""
    _run(code)


@pytest.mark.slow
def test_gcn_split_bsr_overlap_subprocess():
    """backend="bsr" over the SPLIT blocked tables (interior over local
    columns + boundary over the halo block) inside the 8-device shard_map
    equals the global segment forward — flat and hierarchical."""
    code = _PRELUDE + """
from repro.dist.halo import plan_split_blocked_adjacency
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

cfg = GCNConfig(layer_dims=(16, 32, 7), backend="bsr")
params = gcn_init(jax.random.PRNGKey(0), cfg)
ref = np.asarray(gcn_forward(params, jnp.asarray(x), senders, receivers,
                             jnp.asarray(w), GCNConfig(layer_dims=(16, 32, 7)),
                             NO_POLICY))

# flat
plan = get_halo_plan(part, g.edge_index, w)
ia, bd = plan_split_blocked_adjacency(plan)
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
iv, ic, il = ia.device_arrays(); bv, bc, bl = bd.device_arrays()
xb = jnp.asarray(relocate_node_array(plan, x))
pol0 = ShardingPolicy(comm="halo")
def body(fe, a, b, c, d, v1, c1, l1, v2, c2, l2):
    pol = pol0.bind_halo(a[0])
    return gcn_forward(params, fe[0], b[0], c[0], d[0], cfg, pol,
                       adjacency=(v1[0], c1[0], l1[0]),
                       adjacency_boundary=(v2[0], c2[0], l2[0]))[None]
f = jax.shard_map(body, mesh=mesh, in_specs=(P("model"),) * 11,
                  out_specs=P("model"), check_vma=False)
out = restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew, iv, ic, il, bv, bc, bl)))
err = np.abs(out - ref).max()
assert err < 1e-3, ("flat", err)

# hierarchical 2x4 with a bf16 wire on top
plan_h = build_halo_plan(part, g.edge_index, w, axes=("pod", "model"), pods=2)
ia, bd = plan_split_blocked_adjacency(plan_h)
mesh_h = make_mesh((2, 4), ("pod", "model"))
sloc, srem, sl, rl, ew = plan_h.device_arrays()
iv, ic, il = ia.device_arrays(); bv, bc, bl = bd.device_arrays()
xb = jnp.asarray(relocate_node_array(plan_h, x))
pol_h = ShardingPolicy(comm="halo", halo_axes=("pod", "model"), halo_payload="bf16")
def body_h(fe, a, a2, b, c, d, v1, c1, l1, v2, c2, l2):
    pol = pol_h.bind_halo(send_loc=a[0], send_rem=a2[0])
    return gcn_forward(params, fe[0], b[0], c[0], d[0], cfg, pol,
                       adjacency=(v1[0], c1[0], l1[0]),
                       adjacency_boundary=(v2[0], c2[0], l2[0]))[None]
f = jax.shard_map(body_h, mesh=mesh_h, in_specs=(P(("pod", "model")),) * 12,
                  out_specs=P(("pod", "model")), check_vma=False)
out = restore_node_array(plan_h, np.asarray(
    f(xb, sloc, srem, sl, rl, ew, iv, ic, il, bv, bc, bl)))
err_h = np.abs(out - ref).max()
assert err_h < 1e-2, ("hier bf16", err_h)
print("OK", err, err_h)
"""
    _run(code)


@pytest.mark.slow
def test_pna_payload_bf16_subprocess():
    """PNA ships its neighbor table through the same quantized wire: bf16
    payload matches the fp32 global forward within 1e-2 (PNA keeps the
    combined gather — no interior/boundary split — so the payload is the
    whole overlap story for it)."""
    code = _PRELUDE + """
from repro.models.pna import PNAConfig, pna_forward, pna_init

plan = get_halo_plan(part, g.edge_index, w)
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
xb = jnp.asarray(relocate_node_array(plan, x))
cfg = PNAConfig(n_layers=2, d_hidden=32, d_in=16, d_out=3)
params = pna_init(jax.random.PRNGKey(1), cfg)
ref = np.asarray(pna_forward(params, jnp.asarray(x), senders, receivers, cfg, NO_POLICY))

def run(pol0):
    def body(fe, a, b, c, d):
        pol = pol0.bind_halo(a)
        mask = (d > 0).astype(jnp.float32)
        return pna_forward(params, fe, b, c, cfg, pol, edge_mask=mask)
    f = jax.shard_map(
        lambda fe, a, b, c, d: body(fe[0], a[0], b[0], c[0], d[0])[None],
        mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
    )
    return restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew)))

fp32 = run(ShardingPolicy(comm="halo"))
assert np.abs(fp32 - ref).max() < 1e-3
bf16 = run(ShardingPolicy(comm="halo", halo_payload="bf16"))
# PNA's min/max aggregators pass wire rounding straight through (no
# averaging) and the std/scaler terms amplify it — measured ~0.016 max-abs
# vs the GCN's ~0.004, so 5e-2 abs with a 1% relative-L2 guard.
err = np.abs(bf16 - ref).max()
rel = np.linalg.norm(bf16 - ref) / np.linalg.norm(ref)
assert err < 5e-2 and rel < 1e-2, (err, rel)
print("OK", err)
"""
    _run(code)
