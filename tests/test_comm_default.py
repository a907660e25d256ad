"""The default GNN communication path (DESIGN.md §8): halo vs broadcast.

Pins the PR-2 contract: full-graph `build_cell` GNN cells default to the
halo exchange, model forwards produce IDENTICAL outputs under the halo and
broadcast schedules (fp32 tolerance), and the halo default moves strictly
fewer bytes than the broadcast escape hatch on the 8-device mesh.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


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
plan = get_halo_plan(part, g.edge_index, w)
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
xb = jnp.asarray(relocate_node_array(plan, x))
senders = jnp.asarray(g.edge_index[0]); receivers = jnp.asarray(g.edge_index[1])
halo_pol = ShardingPolicy(comm="halo")
"""


@pytest.mark.slow
def test_gcn_halo_equals_broadcast_subprocess():
    """The paper GCN: halo shard_map forward == global forward, per node."""
    code = _PRELUDE + """
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow="feature_first")
params = gcn_init(jax.random.PRNGKey(0), cfg)
ref = np.asarray(gcn_forward(params, jnp.asarray(x), senders, receivers,
                             jnp.asarray(w), cfg, NO_POLICY))

def body(fe, a, b, c, d):
    pol = halo_pol.bind_halo(a)
    return gcn_forward(params, fe, b, c, d, cfg, pol)

f = jax.shard_map(
    lambda fe, a, b, c, d: body(fe[0], a[0], b[0], c[0], d[0])[None],
    mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
)
out = restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew)))
err = np.abs(out - ref).max()
assert err < 1e-4, err
print("OK", err)
"""
    _run(code)


@pytest.mark.slow
def test_pna_halo_equals_broadcast_subprocess():
    """PNA (mean/max/min/std aggregators + degree scalers): halo == global.
    Exercises the masked multi-aggregator path (plan padding edges)."""
    code = _PRELUDE + """
from repro.models.pna import PNAConfig, pna_forward, pna_init

cfg = PNAConfig(n_layers=2, d_hidden=32, d_in=16, d_out=3)
params = pna_init(jax.random.PRNGKey(1), cfg)
ref = np.asarray(pna_forward(params, jnp.asarray(x), senders, receivers, cfg, NO_POLICY))

def body(fe, a, b, c, d):
    pol = halo_pol.bind_halo(a)
    mask = (d > 0).astype(jnp.float32)
    return pna_forward(params, fe, b, c, cfg, pol, edge_mask=mask)

f = jax.shard_map(
    lambda fe, a, b, c, d: body(fe[0], a[0], b[0], c[0], d[0])[None],
    mesh=mesh, in_specs=(P("model"),) * 5, out_specs=P("model"), check_vma=False,
)
out = restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew)))
err = np.abs(out - ref).max()
# fp32 tolerance: the std aggregator's E[x^2]-E[x]^2 cancellation amplifies
# reduction-order differences between the sharded and global programs.
assert err < 1e-3, err
print("OK", err)
"""
    _run(code)


@pytest.mark.slow
def test_egnn_halo_equals_broadcast_subprocess():
    """EGNN (coordinate + feature updates): halo == global, both outputs."""
    code = _PRELUDE + """
from repro.models.egnn import EGNNConfig, egnn_forward, egnn_init

cfg = EGNNConfig(n_layers=2, d_hidden=24, d_in=16, d_out=2)
params = egnn_init(jax.random.PRNGKey(2), cfg)
pos = np.random.default_rng(3).standard_normal((g.n_nodes, 3)).astype(np.float32)
pb = jnp.asarray(relocate_node_array(plan, pos))
ref, ref_x = egnn_forward(params, jnp.asarray(x), jnp.asarray(pos), senders, receivers, cfg, NO_POLICY)
ref, ref_x = np.asarray(ref), np.asarray(ref_x)

def body(fe, po, a, b, c, d):
    pol = halo_pol.bind_halo(a)
    mask = (d > 0).astype(jnp.float32)
    return egnn_forward(params, fe, po, b, c, cfg, pol, edge_mask=mask)

f = jax.shard_map(
    lambda fe, po, a, b, c, d: tuple(o[None] for o in body(fe[0], po[0], a[0], b[0], c[0], d[0])),
    mesh=mesh, in_specs=(P("model"),) * 6, out_specs=(P("model"), P("model")),
    check_vma=False,
)
out_h, out_x = f(xb, pb, si, sl, rl, ew)
err = max(
    np.abs(restore_node_array(plan, np.asarray(out_h)) - ref).max(),
    np.abs(restore_node_array(plan, np.asarray(out_x)) - ref_x).max(),
)
assert err < 1e-4, err
print("OK", err)
"""
    _run(code)


@pytest.mark.slow
def test_default_cell_wire_below_broadcast_subprocess():
    """Acceptance pin: the default full-graph cell is halo, and its dry-run
    bytes-moved is strictly below the broadcast schedule on 8 devices —
    both analytically (k·s_max < (k−1)·n_local rows) and in the compiled
    HLO's per-device collective bytes."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax
from repro.configs import get_arch
from repro.launch.steps import build_cell, collective_bytes, exchange_accounting
from repro.launch.mesh import make_mesh

mesh = make_mesh((1, 8), ("data", "model"))
spec = get_arch("pna")
shape = spec.shapes["full_graph_sm"]
cell = build_cell(spec, shape, mesh)                    # the default
assert cell.comm == "halo", cell.comm
ex = exchange_accounting(cell, shape)
assert ex["halo_rows_per_device"] < ex["broadcast_rows_per_device"], ex
assert ex["wire_fraction"] < 1.0, ex
halo = collective_bytes(cell.lower(mesh).compile().as_text())
cell_b = build_cell(spec, shape, mesh, comm="broadcast")
assert cell_b.comm == "broadcast"
bcast = collective_bytes(cell_b.lower(mesh).compile().as_text())
assert halo["all-gather"] < bcast["all-gather"], (halo, bcast)
assert halo["total"] < bcast["total"], (halo, bcast)
print("OK", ex["wire_fraction"], halo["total"] / max(bcast["total"], 1))
"""
    _run(code)


def test_default_cell_compiles_one_device():
    """The halo default degenerates cleanly to k=1 (s_max=0, empty exchange)
    on the local mesh — the same code path unit tests and CPU examples use."""
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_cell

    mesh = make_local_mesh()
    spec = get_arch("pna")
    cell = build_cell(spec, spec.shapes["full_graph_sm"], mesh)
    assert cell.comm == "halo" and cell.halo_plan.k == 1
    assert cell.halo_plan.s_max == 0
    compiled = cell.lower(mesh).compile()
    assert (compiled.cost_analysis() or {}).get("flops", 0) > 0
