"""Halo-exchange plan + collective: invariants and exact equivalence."""
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


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(64, 400),
    e=st.integers(100, 2000),
    k=st.sampled_from([4, 8]),
    seed=st.integers(0, 50),
)
def test_halo_plan_accounts_every_edge(n, e, k, seed):
    g = citation_like(n, e, seed=seed)
    part = partition_graph(n, g.edge_index, k, method="bfs", seed=seed)
    plan = build_halo_plan(part, g.edge_index)
    # Every original edge appears exactly once across the device edge lists.
    total_valid = int((plan.edge_w > 0).sum())
    assert total_valid == e
    # Receivers are always local rows; senders index [local ‖ halo].
    assert plan.receivers_l.max() < plan.n_local
    assert plan.senders_l.max() < plan.n_local + plan.k * plan.s_max
    # The permutation is a bijection.
    assert np.array_equal(np.sort(plan.perm), np.arange(n))


def test_halo_plan_wire_volume_below_broadcast():
    g = citation_like(2000, 12000, seed=1)
    part = partition_graph(2000, g.edge_index, 8, method="bfs", seed=0, refine=True)
    plan = build_halo_plan(part, g.edge_index)
    halo_rows = plan.k * plan.s_max          # per device
    broadcast_rows = (plan.k - 1) * plan.n_local
    assert halo_rows < broadcast_rows


@pytest.mark.slow
def test_halo_aggregate_equals_global_subprocess():
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_graph
from repro.dist.halo import build_halo_plan, halo_aggregate
from repro.graph.generators import citation_like
from repro.graph.ops import aggregate
from repro.launch.mesh import make_mesh

g = citation_like(500, 3000, seed=3)
w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32)
part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
plan = build_halo_plan(part, g.edge_index, w)
d = 16
z = np.random.default_rng(1).standard_normal((g.n_nodes, d)).astype(np.float32)
zb = np.zeros((8, plan.n_local, d), np.float32)
sizes = np.bincount(part.assignment, minlength=8)
off = 0
for i in range(8):
    zb[i, :sizes[i]] = z[plan.perm[off:off+sizes[i]]]
    off += sizes[i]
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
ref = np.asarray(aggregate(jnp.asarray(z), jnp.asarray(g.edge_index[0]),
                           jnp.asarray(g.edge_index[1]), g.n_nodes, jnp.asarray(w)))
refb = np.zeros_like(zb)
off = 0
for i in range(8):
    refb[i, :sizes[i]] = ref[plan.perm[off:off+sizes[i]]]
    off += sizes[i]
for via in ("all_gather", "ppermute"):    # both collective lowerings
    f = jax.shard_map(
        lambda zl, a, b, c, dd: halo_aggregate(zl[0], a[0], b[0], c[0], dd[0], "model", via=via)[None],
        mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
    )
    out = np.asarray(f(jnp.asarray(zb), si, sl, rl, ew))
    err = np.abs(out - refb).max()
    assert err < 1e-4, (via, err)
print("HALO_OK", err)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert "HALO_OK" in out.stdout, out.stderr[-1500:]


def test_grouped_moe_equals_flat():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.nn.moe import MoEConfig, moe_apply, moe_init

    key = jax.random.PRNGKey(0)
    cfg1 = MoEConfig(num_experts=8, top_k=2, d_model=32, d_ff=64, capacity_factor=8.0, groups=1)
    cfg4 = dataclasses.replace(cfg1, groups=4)
    p = moe_init(key, cfg1)
    x = jax.random.normal(key, (128, 32))
    y1, a1 = moe_apply(p, x, cfg1)
    y4, a4 = moe_apply(p, x, cfg4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=1e-6)
    assert abs(float(a1 - a4)) < 1e-6
