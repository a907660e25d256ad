"""Numerical equivalence of the three GCN aggregation backends and the
Pallas bsr_spmm kernel against `kernels/ref.py` — the regression net for
later kernel-perf PRs (interpret-mode Pallas on CPU, native on TPU)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quant import QuantConfig
from repro.graph.structure import (
    blocked_adjacency,
    blocked_stats,
    locality_block_order,
    permute_edge_index,
    relocate_rows,
    restore_rows,
)
from repro.kernels.ops import bsr_spmm
from repro.kernels.ref import bsr_spmm_ref
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

RNG = np.random.default_rng(7)
SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _dense_adj(n: int, ei: np.ndarray, w: np.ndarray) -> np.ndarray:
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (ei[1], ei[0]), w)       # A[r, s] = w: O = A·Z aggregates
    return a


def _graph(n: int, e: int, seed: int):
    r = np.random.default_rng(seed)
    ei = r.integers(0, n, size=(2, e)).astype(np.int32)
    w = (np.abs(r.standard_normal(e)) + 0.1).astype(np.float32)
    return ei, w


# ------------------------------------------------------- backend equivalence
@pytest.mark.parametrize("dims", [(24, 16, 8), (12, 32, 4)])
@pytest.mark.parametrize("dataflow", ["feature_first", "aggregation_first"])
def test_gcn_backends_agree(dims, dataflow):
    n, e = 256, 1200                       # n multiple of 128 → bsr-ready
    ei, w = _graph(n, e, seed=0)
    x = RNG.standard_normal((n, dims[0])).astype(np.float32)
    cfgs = {
        b: GCNConfig(layer_dims=dims, dataflow=dataflow, backend=b)
        for b in ("segment", "dense", "bsr")
    }
    params = gcn_init(jax.random.PRNGKey(0), cfgs["segment"])
    ba = blocked_adjacency(n, ei, w, block=128)
    outs = {
        "segment": gcn_forward(params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]),
                               jnp.asarray(w), cfgs["segment"]),
        "dense": gcn_forward(params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]),
                             jnp.asarray(w), cfgs["dense"],
                             dense_adj=jnp.asarray(_dense_adj(n, ei, w))),
        "bsr": gcn_forward(params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]),
                           jnp.asarray(w), cfgs["bsr"],
                           adjacency=(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols))),
    }
    ref = np.asarray(outs["segment"])
    for b in ("dense", "bsr"):
        np.testing.assert_allclose(np.asarray(outs[b]), ref, rtol=3e-4, atol=3e-4,
                                   err_msg=f"backend {b} vs segment ({dataflow})")


def test_gcn_segment_matches_numpy_oracle():
    """One layer, hand-rolled numpy: Ã·(X·W) + b, relu-free last layer."""
    n, e, d_in, d_out = 64, 300, 8, 3
    ei, w = _graph(n, e, seed=3)
    x = RNG.standard_normal((n, d_in)).astype(np.float32)
    cfg = GCNConfig(layer_dims=(d_in, d_out), dataflow="feature_first")
    params = gcn_init(jax.random.PRNGKey(1), cfg)
    out = gcn_forward(params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]),
                      jnp.asarray(w), cfg)
    a = _dense_adj(n, ei, w)
    ref = a @ (x @ np.asarray(params["w0"])) + np.asarray(params["b0"])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------------- ragged / fused bsr layers
@pytest.mark.parametrize("dataflow", ["feature_first", "aggregation_first"])
def test_gcn_bsr_nonmultiple_n_matches_segment(dataflow):
    """N not a multiple of 128 (ragged tail block): the fused bsr forward,
    fed the BlockedAdjacency directly, equals the segment reference."""
    n, e, dims = 300, 1500, (20, 24, 6)
    ei, w = _graph(n, e, seed=11)
    x = RNG.standard_normal((n, dims[0])).astype(np.float32)
    params = gcn_init(jax.random.PRNGKey(3), GCNConfig(layer_dims=dims))
    ba = blocked_adjacency(n, ei, w, block=128)
    args = (params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]), jnp.asarray(w))
    seg = gcn_forward(*args, GCNConfig(layer_dims=dims, dataflow=dataflow))
    out = gcn_forward(
        *args, GCNConfig(layer_dims=dims, dataflow=dataflow, backend="bsr"),
        adjacency=ba,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(seg), rtol=3e-4, atol=3e-4)


def test_gcn_bsr_matches_segment_under_fake_quant():
    """Fake-quantized weights/activations flow through the fused kernel the
    same as through the segment path (quant happens outside the kernel)."""
    n, e, dims = 384, 2000, (16, 32, 5)
    ei, w = _graph(n, e, seed=12)
    x = RNG.standard_normal((n, dims[0])).astype(np.float32)
    q = QuantConfig(4, 4, enabled=True)
    params = gcn_init(jax.random.PRNGKey(4), GCNConfig(layer_dims=dims))
    ba = blocked_adjacency(n, ei, w, block=128)
    args = (params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]), jnp.asarray(w))
    seg = gcn_forward(*args, GCNConfig(layer_dims=dims, quant=q))
    out = gcn_forward(
        *args, GCNConfig(layer_dims=dims, quant=q, backend="bsr"), adjacency=ba
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(seg), rtol=3e-4, atol=3e-4)


def test_gcn_backend_argument_validation():
    """Up-front ValueErrors instead of asserts/mid-trace failures."""
    n, e, dims = 64, 200, (8, 4)
    ei, w = _graph(n, e, seed=13)
    x = RNG.standard_normal((n, dims[0])).astype(np.float32)
    params = gcn_init(jax.random.PRNGKey(5), GCNConfig(layer_dims=dims))
    args = (params, x, jnp.asarray(ei[0]), jnp.asarray(ei[1]), jnp.asarray(w))
    with pytest.raises(ValueError, match="unknown GCN backend"):
        gcn_forward(*args, GCNConfig(layer_dims=dims, backend="sparse"))
    with pytest.raises(ValueError, match="requires adjacency"):
        gcn_forward(*args, GCNConfig(layer_dims=dims, backend="bsr"))
    with pytest.raises(ValueError, match="BlockedAdjacency"):
        gcn_forward(*args, GCNConfig(layer_dims=dims, backend="bsr"),
                    adjacency=np.zeros((4, 4)))
    with pytest.raises(ValueError, match="vals"):
        gcn_forward(*args, GCNConfig(layer_dims=dims, backend="bsr"),
                    adjacency=(np.zeros((4, 4)), np.zeros(3)))
    with pytest.raises(ValueError, match="dense_adj"):
        gcn_forward(*args, GCNConfig(layer_dims=dims, backend="dense"))


def test_locality_reorder_improves_blocking():
    """The locality permutation on a shuffled power-law community graph cuts
    both the nonzero-tile count and the dense-T executed-tile count ≥ 2×
    (stats-only — no tile materialization), and the blocked forward over the
    reordered graph matches the segment forward after restore."""
    from repro.graph.generators import citation_like

    n, e = 4096, 16384
    g = citation_like(n, e, n_labels=32, homophily=0.9, seed=1)
    shuf = np.random.default_rng(7).permutation(n).astype(np.int64)
    ei = permute_edge_index(shuf, g.edge_index)
    base = blocked_stats(n, ei)
    perm = locality_block_order(n, ei, block=128)
    reord = blocked_stats(n, permute_edge_index(perm, ei))
    assert reord["nnz_blocks"] * 2 <= base["nnz_blocks"], (base, reord)
    assert reord["nnz_blocks"] * 2 <= base["dense_tiles"], (base, reord)

    # numerical equivalence through the permutation, on a small subgraph
    n2, e2 = 384, 1600
    ei2, w2 = _graph(n2, e2, seed=14)
    perm2 = locality_block_order(n2, ei2, block=128)
    ba = blocked_adjacency(n2, permute_edge_index(perm2, ei2), w2, block=128)
    dims = (12, 8, 3)
    params = gcn_init(jax.random.PRNGKey(6), GCNConfig(layer_dims=dims))
    x = RNG.standard_normal((n2, dims[0])).astype(np.float32)
    seg = gcn_forward(params, x, jnp.asarray(ei2[0]), jnp.asarray(ei2[1]),
                      jnp.asarray(w2), GCNConfig(layer_dims=dims))
    out_p = gcn_forward(
        params, jnp.asarray(relocate_rows(perm2, x)),
        jnp.asarray(ei2[0]), jnp.asarray(ei2[1]), jnp.asarray(w2),
        GCNConfig(layer_dims=dims, backend="bsr"), adjacency=ba,
    )
    np.testing.assert_allclose(
        restore_rows(perm2, np.asarray(out_p)), np.asarray(seg), rtol=3e-4, atol=3e-4
    )


@pytest.mark.slow
def test_gcn_bsr_halo_equals_segment_subprocess():
    """backend="bsr" inside the 8-device halo shard_map path (the per-shard
    blocked adjacency over [local ‖ halo]) produces the same logits as the
    global segment forward — both dataflow orders."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_graph
from repro.dist.halo import get_halo_plan, plan_blocked_adjacency, plan_blocked_shape, relocate_node_array, restore_node_array
from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.graph.generators import citation_like
from repro.launch.mesh import make_mesh
from repro.models.gcn import GCNConfig, gcn_forward, gcn_init

g = citation_like(400, 2400, seed=5)
w = np.abs(np.random.default_rng(0).standard_normal(g.n_edges)).astype(np.float32) + 0.1
part = partition_graph(g.n_nodes, g.edge_index, 8, method="bfs", seed=0, refine=True)
plan = get_halo_plan(part, g.edge_index, w)
ba = plan_blocked_adjacency(plan)
shp = plan_blocked_shape(plan)
assert shp["max_nnzb"] == ba.max_nnzb and shp["nnz_blocks"] == ba.nnz_blocks
assert plan_blocked_adjacency(plan) is ba          # cached next to the plan
mesh = make_mesh((8,), ("model",))
si, sl, rl, ew = plan.device_arrays()
bv, bc, bl = ba.device_arrays()
x = np.random.default_rng(1).standard_normal((g.n_nodes, 16)).astype(np.float32)
xb = jnp.asarray(relocate_node_array(plan, x))
halo_pol = ShardingPolicy(comm="halo")
worst = 0.0
for dataflow in ("feature_first", "aggregation_first"):
    cfg = GCNConfig(layer_dims=(16, 32, 7), dataflow=dataflow, backend="bsr")
    params = gcn_init(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(gcn_forward(params, jnp.asarray(x), jnp.asarray(g.edge_index[0]),
                                 jnp.asarray(g.edge_index[1]), jnp.asarray(w),
                                 GCNConfig(layer_dims=(16, 32, 7), dataflow=dataflow), NO_POLICY))
    def body(fe, a, b, c, d, v, co, le):
        pol = halo_pol.bind_halo(a)
        return gcn_forward(params, fe, b, c, d, cfg, pol, adjacency=(v, co, le))
    f = jax.shard_map(
        lambda fe, a, b, c, d, v, co, le: body(fe[0], a[0], b[0], c[0], d[0], v[0], co[0], le[0])[None],
        mesh=mesh, in_specs=(P("model"),) * 8, out_specs=P("model"), check_vma=False,
    )
    out = restore_node_array(plan, np.asarray(f(xb, si, sl, rl, ew, bv, bc, bl)))
    err = np.abs(out - ref).max()
    assert err < 1e-4, (dataflow, err)
    worst = max(worst, err)

# hierarchical (2 pods x 4): the per-shard blocking spans the member-block
# table (neighbor_table_rows, NOT halo_rows_per_device) — geometry + numerics
from repro.dist.halo import build_halo_plan
plan_h = build_halo_plan(part, g.edge_index, w, axes=("pod", "model"), pods=2)
assert plan_h.neighbor_table_rows == plan_h.n_local + plan_h.k_model * plan_h.block_rows
ba_h = plan_blocked_adjacency(plan_h)
assert ba_h.n_cols == plan_h.neighbor_table_rows
assert int(plan_h.senders_l.max()) < ba_h.n_cols
mesh_h = make_mesh((2, 4), ("pod", "model"))
sloc, srem, sl, rl, ew2 = plan_h.device_arrays()
bv, bc, bl = ba_h.device_arrays()
xb = jnp.asarray(relocate_node_array(plan_h, x))
pol0 = ShardingPolicy(comm="halo", halo_axes=("pod", "model"))
cfg = GCNConfig(layer_dims=(16, 32, 7), backend="bsr")
params = gcn_init(jax.random.PRNGKey(0), cfg)
ref = np.asarray(gcn_forward(params, jnp.asarray(x), jnp.asarray(g.edge_index[0]),
                             jnp.asarray(g.edge_index[1]), jnp.asarray(w),
                             GCNConfig(layer_dims=(16, 32, 7)), NO_POLICY))
def body_h(fe, a, a2, b, c, d, v, co, le):
    pol = pol0.bind_halo(send_loc=a[0], send_rem=a2[0])
    return gcn_forward(params, fe[0], b[0], c[0], d[0], cfg, pol,
                       adjacency=(v[0], co[0], le[0]))[None]
f = jax.shard_map(body_h, mesh=mesh_h, in_specs=(P(("pod", "model")),) * 9,
                  out_specs=P(("pod", "model")), check_vma=False)
out = restore_node_array(plan_h, np.asarray(f(xb, sloc, srem, sl, rl, ew2, bv, bc, bl)))
err = np.abs(out - ref).max()
assert err < 1e-4, ("hier", err)
print("OK", max(worst, err))
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])


# ------------------------------------------------------------ bsr_spmm extra
def test_bsr_spmm_feature_pad_path():
    """F not a multiple of the tile exercises the pad/slice wrapper path."""
    n, e, f = 256, 900, 50
    ei, w = _graph(n, e, seed=1)
    ba = blocked_adjacency(n, ei, w, block=128)
    z = jnp.asarray(RNG.standard_normal((ba.n_padded, f)), jnp.float32)
    out = bsr_spmm(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), z)
    zp = jnp.pad(z, ((0, 0), (0, 128 - f)))
    ref = bsr_spmm_ref(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), zp)[:, :f]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(
    n=st.sampled_from([128, 256, 384]),
    e=st.integers(50, 2000),
    f=st.sampled_from([16, 64, 130]),
    seed=st.integers(0, 99),
)
def test_bsr_spmm_vs_segment_aggregate(n, e, f, seed):
    """Kernel == segment-op aggregation on random graphs (system contract)."""
    from repro.graph.ops import aggregate

    ei, w = _graph(n, e, seed)
    ba = blocked_adjacency(n, ei, w, block=128)
    r = np.random.default_rng(seed + 1)
    z = jnp.asarray(r.standard_normal((ba.n_padded, f)), jnp.float32)
    out = bsr_spmm(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), z)[:n]
    seg = aggregate(z[:n], jnp.asarray(ei[0]), jnp.asarray(ei[1]), n, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(seg), rtol=5e-4, atol=5e-4)
