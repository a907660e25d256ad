"""Hierarchical (pod, model) halo exchange: plan invariants, tier split,
numpy emulation of the two-phase collective, plan-cache keying, and the
8-device 2×4 equivalence/wire acceptance (docs/communication.md).
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


def _emulated_halo_tables(plan, zb: np.ndarray) -> np.ndarray:
    """Pure-numpy construction of every device's [local ‖ halo] neighbor
    table under the hierarchical member-block layout (the HaloPlan contract):
    member block m' = [send_loc rows of (p, m') ‖ per pod q: send_rem rows
    of (q, m')]. The shard_map collectives must produce exactly this."""
    k, km, pods = plan.k, plan.k_model, plan.n_pods
    width = plan.n_local + km * plan.block_rows
    tables = np.zeros((k, width) + zb.shape[2:], zb.dtype)
    for g in range(k):
        p = g // km
        parts = [zb[g]]
        for m in range(km):
            member = p * km + m
            parts.append(zb[member][plan.send_loc[member]])
            for q in range(pods):
                parts.append(zb[q * km + m][plan.send_rem[q * km + m]])
        tables[g] = np.concatenate(parts, axis=0)
    return tables


def _blocked(plan, x: np.ndarray) -> np.ndarray:
    out = np.zeros((plan.k, plan.n_local) + x.shape[1:], x.dtype)
    off = 0
    for b in range(plan.k):
        sz = int(plan.part_sizes[b])
        out[b, :sz] = x[plan.perm[off:off + sz]]
        off += sz
    return out


# ------------------------------------------------------------ plan properties
@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(64, 400),
    e=st.integers(100, 2000),
    kp=st.sampled_from([(4, 2), (8, 2), (8, 4)]),
    seed=st.integers(0, 50),
)
def test_hier_plan_accounts_every_edge(n, e, kp, seed):
    k, pods = kp
    g = citation_like(n, e, seed=seed)
    part = partition_graph(n, g.edge_index, k, method="bfs", seed=seed)
    plan = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=pods)
    assert plan.is_hierarchical and plan.n_pods == pods and plan.k_model == k // pods
    # Every original edge appears exactly once across the device edge lists.
    assert int((plan.edge_w > 0).sum()) == e
    # Receivers are local rows; senders index the hierarchical table.
    assert plan.receivers_l.max() < plan.n_local
    assert plan.senders_l.max() < plan.n_local + plan.k_model * plan.block_rows
    # The permutation is a bijection.
    assert np.array_equal(np.sort(plan.perm), np.arange(n))
    # Per-tier pads never exceed the flat boundary pad it splits.
    assert plan.s_loc <= plan.s_max and plan.s_rem <= plan.s_max
    # Export tables stay in local-row range.
    if plan.s_loc:
        assert plan.send_loc.min() >= 0 and plan.send_loc.max() < plan.n_local
    if plan.s_rem:
        assert plan.send_rem.min() >= 0 and plan.send_rem.max() < plan.n_local


def test_hier_aggregate_matches_global_numpy_emulation():
    """The member-block addressing is exact: emulating the two-phase exchange
    in numpy and aggregating reproduces the global aggregate bit-for-bit."""
    from repro.graph.ops import aggregate
    import jax.numpy as jnp

    g = citation_like(400, 2400, seed=5)
    w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
    part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
    plan = build_halo_plan(part, g.edge_index, w, axes=("pod", "model"), pods=2)
    d = 16
    z = np.random.default_rng(1).standard_normal((g.n_nodes, d)).astype(np.float32)
    zb = _blocked(plan, z)
    tables = _emulated_halo_tables(plan, zb)
    out = np.zeros_like(zb)
    for dev in range(plan.k):
        msg = tables[dev][plan.senders_l[dev]] * plan.edge_w[dev][:, None]
        np.add.at(out[dev], plan.receivers_l[dev], msg)
    ref = np.asarray(aggregate(jnp.asarray(z), jnp.asarray(g.edge_index[0]),
                               jnp.asarray(g.edge_index[1]), g.n_nodes, jnp.asarray(w)))
    np.testing.assert_allclose(out, _blocked(plan, ref), atol=1e-4)


def test_hier_wire_tiers_beat_flat():
    """The acceptance inequality: strictly fewer rows cross the inter-pod
    fabric than under the flat single-axis schedule, and the cheap tier's
    pad is at most the global worst case it used to pay."""
    g = citation_like(2000, 12000, seed=1)
    part = partition_graph(2000, g.edge_index, 8, method="bfs", seed=0, refine=True)
    flat = build_halo_plan(part, g.edge_index)
    hier = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2)
    # Same partition → same flat baseline numbers on both plans.
    assert hier.s_max == flat.s_max and hier.n_local == flat.n_local
    assert hier.inter_pod_rows_crossing < hier.flat_inter_pod_rows_crossing
    assert hier.s_loc <= flat.s_max
    assert hier.halo_rows_per_device < hier.broadcast_rows_per_device
    # Tier arithmetic is self-consistent.
    assert hier.inter_pod_rows_per_device == hier.n_pods * hier.s_rem
    assert hier.intra_pod_rows_per_device == hier.k_model * hier.block_rows
    assert hier.halo_rows_per_device == (
        hier.inter_pod_rows_per_device + hier.intra_pod_rows_per_device
    )


def test_hier_plan_degenerate_pods():
    g = citation_like(150, 900, seed=2)
    part = partition_graph(150, g.edge_index, 4, method="bfs", seed=0)
    # pods=1: every cut edge is intra-pod; nothing crosses the (absent) fabric.
    p1 = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=1)
    assert p1.s_rem == 0 and p1.inter_pod_rows_per_device == 0
    assert p1.s_loc == p1.s_max                  # one pod ⇒ tiers collapse
    assert int((p1.edge_w > 0).sum()) == 900
    # pods=k: singleton pods; every cut edge crosses, the cheap tier is empty.
    pk = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=4)
    assert pk.s_loc == 0 and pk.k_model == 1
    assert pk.s_rem == pk.s_max
    assert int((pk.edge_w > 0).sum()) == 900


def test_hier_plan_validation():
    g = citation_like(64, 300, seed=1)
    part = partition_graph(64, g.edge_index, 4, method="block")
    with pytest.raises(ValueError):
        build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=3)
    with pytest.raises(ValueError):
        build_halo_plan(part, g.edge_index, pods=2)          # one axis, 2 pods
    with pytest.raises(ValueError):
        build_halo_plan(part, g.edge_index, axes=("model", "model"), pods=2)
    with pytest.raises(ValueError):
        build_halo_plan(part, g.edge_index, axes=("a", "b", "c"))


def test_hier_device_arrays_arity():
    g = citation_like(100, 500, seed=3)
    part = partition_graph(100, g.edge_index, 4, method="bfs", seed=0)
    flat = build_halo_plan(part, g.edge_index)
    hier = build_halo_plan(part, g.edge_index, axes=("pod", "model"), pods=2)
    assert len(flat.device_arrays()) == 4 and len(flat.abstract_inputs()) == 4
    assert len(hier.device_arrays()) == 5 and len(hier.abstract_inputs()) == 5
    sloc, srem = hier.abstract_inputs()[:2]
    assert sloc.shape == (4, hier.s_loc) and srem.shape == (4, hier.s_rem)


# --------------------------------------------------------------- plan cache
def test_plan_cache_flat_and_hier_coexist():
    """Single-axis and hierarchical plans for the same graph live side by
    side under (graph_key, k, axes) without cross-invalidation."""
    from repro.dist import halo

    halo.invalidate_halo_plans()
    g = citation_like(120, 700, seed=7)
    part = partition_graph(120, g.edge_index, 4, method="bfs", seed=0)
    flat = halo.get_halo_plan(part, g.edge_index)
    hier = halo.get_halo_plan(part, g.edge_index, pods=2)
    assert flat is not hier and not flat.is_hierarchical and hier.is_hierarchical
    # Both hit their own entries; neither evicted the other.
    assert halo.get_halo_plan(part, g.edge_index) is flat
    assert halo.get_halo_plan(part, g.edge_index, pods=2) is hier
    assert halo.plan_cache_stats()["size"] >= 2
    # The explicit axes-tuple spelling resolves to the same cache entry.
    assert halo.get_halo_plan(part, g.edge_index, mesh_axis=("pod", "model"), pods=2) is hier
    # Graph-level invalidation drops BOTH kinds (a re-partition stales both).
    evicted = halo.invalidate_halo_plans(
        halo.graph_fingerprint(part.n_nodes, g.edge_index, None, part.assignment)
    )
    assert evicted >= 2
    assert halo.get_halo_plan(part, g.edge_index) is not flat
    assert halo.get_halo_plan(part, g.edge_index, pods=2) is not hier


def test_plan_cache_distinct_pod_counts_never_collide():
    """The member-block layout depends on the pod count, so pods=2 and
    pods=4 plans of the SAME k=8 partition must cache separately (the key's
    axes component is the (axes, pods) pair)."""
    from repro.dist import halo

    halo.invalidate_halo_plans()
    g = citation_like(200, 1200, seed=4)
    part = partition_graph(200, g.edge_index, 8, method="bfs", seed=0)
    p2 = halo.get_halo_plan(part, g.edge_index, pods=2)
    p4 = halo.get_halo_plan(part, g.edge_index, pods=4)
    assert p2 is not p4
    assert p2.n_pods == 2 and p4.n_pods == 4
    # Both stay independently hot.
    assert halo.get_halo_plan(part, g.edge_index, pods=2) is p2
    assert halo.get_halo_plan(part, g.edge_index, pods=4) is p4
    # Same collision guard on the launch layer's string-keyed entry point.
    from repro.launch.steps import _shape_halo_plan

    s2 = _shape_halo_plan(200, 1200, 8, pods=2)
    s4 = _shape_halo_plan(200, 1200, 8, pods=4)
    assert s2 is not s4 and s2.n_pods == 2 and s4.n_pods == 4


def test_plan_cache_hier_requires_pods():
    from repro.dist import halo

    g = citation_like(64, 300, seed=1)
    part = partition_graph(64, g.edge_index, 4, method="block")
    with pytest.raises(ValueError):
        halo.get_halo_plan(part, g.edge_index, mesh_axis=("pod", "model"))


# ------------------------------------------------- policy bind validation
def test_policy_hier_bind_and_validation():
    import jax.numpy as jnp

    from repro.dist.policy import ShardingPolicy

    pol = ShardingPolicy(comm="halo", halo_axes=("pod", "model"))
    assert not pol.is_halo
    loc = jnp.asarray([0, 1], jnp.int32)
    rem = jnp.asarray([2], jnp.int32)
    bound = pol.bind_halo(send_loc=loc, send_rem=rem)
    assert bound.is_halo and not pol.is_halo
    with pytest.raises(ValueError):
        pol.bind_halo(loc, send_loc=loc, send_rem=rem)
    with pytest.raises(ValueError):
        pol.bind_halo(send_loc=loc)                    # rem missing
    with pytest.raises(ValueError):
        pol.bind_halo()                                # nothing bound at all


def test_size_one_pod_axis_degenerates_to_flat():
    """A mesh whose pod axis has width 1 is no hierarchy: halo_axes reports
    the flat schedule and build_cell produces a working flat halo cell
    (regression: the hier/flat decision and the plan kind must agree)."""
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import halo_axes, make_halo_mesh
    from repro.launch.steps import build_cell

    mesh = make_halo_mesh(1, jax.device_count())
    assert halo_axes(mesh) == ("model",)
    spec = get_arch("pna")
    cell = build_cell(spec, spec.shapes["full_graph_sm"], mesh)
    assert cell.comm == "halo" and not cell.halo_plan.is_hierarchical
    assert "send_idx" in cell.abstract_args[2]
    compiled = cell.lower(mesh).compile()
    assert (compiled.cost_analysis() or {}).get("flops", 0) > 0


# ----------------------------------------- 8-device 2×4 acceptance (slow)
def _run(code: str) -> None:
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])


_PRELUDE = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_graph
from repro.dist.halo import get_halo_plan, relocate_node_array, restore_node_array
from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.graph.generators import citation_like
from repro.launch.mesh import make_mesh

g = citation_like(400, 2400, seed=5)
w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
flat = get_halo_plan(part, g.edge_index, w)
hier = get_halo_plan(part, g.edge_index, w, pods=2)
assert hier.inter_pod_rows_crossing < hier.flat_inter_pod_rows_crossing
mesh2d = make_mesh((2, 4), ("pod", "model"))
mesh1d = make_mesh((8,), ("model",))
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
AX = ("pod", "model")

def run_hier(fwd):
    sloc, srem, sl, rl, ew = hier.device_arrays()
    xb = jnp.asarray(relocate_node_array(hier, x))
    pol0 = ShardingPolicy(comm="halo", halo_axes=AX)
    f = jax.shard_map(
        lambda fe, a, b, c, d, e: fwd(fe[0], pol0.bind_halo(send_loc=a[0], send_rem=b[0]),
                                      c[0], d[0], e[0])[None],
        mesh=mesh2d, in_specs=(P(AX),) * 6, out_specs=P(AX), check_vma=False,
    )
    return restore_node_array(hier, np.asarray(f(xb, sloc, srem, sl, rl, ew)))

def run_flat(fwd):
    si, sl, rl, ew = flat.device_arrays()
    xb = jnp.asarray(relocate_node_array(flat, x))
    pol0 = ShardingPolicy(comm="halo")
    f = jax.shard_map(
        lambda fe, a, b, c, d: fwd(fe[0], pol0.bind_halo(a[0]), b[0], c[0], d[0])[None],
        mesh=mesh1d, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
    )
    return restore_node_array(flat, np.asarray(f(xb, si, sl, rl, ew)))
"""


@pytest.mark.slow
def test_gcn_hier_equals_flat_equals_broadcast_subprocess():
    """The paper GCN on the 2×4 (pod, model) mesh: hierarchical halo ==
    flat halo == global broadcast forward, per node (fp32 tolerance)."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow="feature_first")
params = gcn_init(jax.random.PRNGKey(0), cfg)
ref = np.asarray(gcn_forward(params, jnp.asarray(x), jnp.asarray(g.edge_index[0]),
                             jnp.asarray(g.edge_index[1]), jnp.asarray(w), cfg, NO_POLICY))

def fwd(fe, pol, s, r, ww):
    return gcn_forward(params, fe, s, r, ww, cfg, pol)

err_h = np.abs(run_hier(fwd) - ref).max()
err_f = np.abs(run_flat(fwd) - ref).max()
assert err_h < 1e-4 and err_f < 1e-4, (err_h, err_f)
print("OK", err_h, err_f)
"""
    _run(code)


@pytest.mark.slow
def test_pna_hier_equals_flat_equals_broadcast_subprocess():
    """PNA (mean/max/min/std aggregators) on the 2×4 mesh: hierarchical ==
    flat == global. Exercises the masked multi-aggregator path with the
    hierarchical padding (edge_w == 0 edges stay inert)."""
    code = _PRELUDE + """
from repro.models.pna import PNAConfig, pna_forward, pna_init

cfg = PNAConfig(n_layers=2, d_hidden=32, d_in=16, d_out=3)
params = pna_init(jax.random.PRNGKey(1), cfg)
ref = np.asarray(pna_forward(params, jnp.asarray(x), jnp.asarray(g.edge_index[0]),
                             jnp.asarray(g.edge_index[1]), cfg, NO_POLICY))

def fwd(fe, pol, s, r, ww):
    return pna_forward(params, fe, s, r, cfg, pol,
                       edge_mask=(ww > 0).astype(jnp.float32))

err_h = np.abs(run_hier(fwd) - ref).max()
err_f = np.abs(run_flat(fwd) - ref).max()
# fp32 tolerance: the std aggregator's E[x^2]-E[x]^2 cancellation amplifies
# reduction-order differences between the sharded and global programs.
assert err_h < 1e-3 and err_f < 1e-3, (err_h, err_f)
print("OK", err_h, err_f)
"""
    _run(code)


@pytest.mark.slow
def test_hier_cell_accounting_subprocess():
    """build_cell on a pod-tiered mesh produces a hierarchical halo cell
    whose dry-run accounting splits the tiers and whose inter-pod crossing
    rows are strictly below the flat schedule's."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax
from repro.configs import get_arch
from repro.launch.steps import build_cell, exchange_accounting
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 1, 4), ("pod", "data", "model"))
spec = get_arch("pna")
shape = spec.shapes["full_graph_sm"]
cell = build_cell(spec, shape, mesh)                    # the default
assert cell.comm == "halo" and cell.halo_plan.is_hierarchical
assert cell.halo_plan.n_pods == 2 and cell.halo_plan.k == 8
ex = exchange_accounting(cell, shape)
assert ex["pods"] == 2 and ex["axes"] == ["pod", "model"]
assert ex["inter_pod_rows_crossing"] < ex["flat_inter_pod_rows_crossing"], ex
assert ex["halo_rows_per_device"] < ex["broadcast_rows_per_device"], ex
compiled = cell.lower(mesh).compile()
assert (compiled.cost_analysis() or {{}}).get("flops", 0) > 0
print("OK", ex["inter_pod_rows_crossing"], ex["flat_inter_pod_rows_crossing"])
"""
    _run(code)
