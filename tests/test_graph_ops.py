"""The scatter-free sorted sum (`repro.graph.ops.sorted_segment_sum`) and
its gather (`sorted_gather`) against `jax.ops.segment_sum` and plain
indexing, forward and gradient, on the CPU.

Both sides sum in float32 in different orders (a tile's contraction, then
its partials, against one pass in edge order), so they agree to a few
float32 ulps of the largest sum: rtol 1e-6, with an absolute floor of 1e-6
of the largest magnitude for sums that cancel."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.graph import ops, sphere
from repro.models import graphcast as gc

D = 8


def _close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * max(np.abs(want).max(), 1e-30))


def _edge_set_ids():
    """The receiver and sender ids of GraphCast's three edge sets at a 10°
    grid and a refinement-2 multimesh, each in its sorted order."""
    g = sphere.build_graph(10.0, 2, 0, 0.6)
    z = g.sizes
    out = {}
    for name, s, r, ns, nr in (("mesh", g.mesh_senders, g.mesh_receivers, z["n_mesh"], z["n_mesh"]),
                               ("g2m", g.g2m_senders, g.g2m_receivers, z["n_grid"], z["n_mesh"]),
                               ("m2g", g.m2g_senders, g.m2g_receivers, z["n_mesh"], z["n_grid"])):
        out[f"{name}_recv"] = (r, nr)
        out[f"{name}_send"] = (np.sort(s, kind="stable"), ns)
    return out


def _hub(rng):
    """Node 3 receives 1500 edges, more than the longest tile."""
    ids = np.sort(np.concatenate([rng.integers(0, 40, 700), np.full(1500, 3)]))
    return ids, 40


def _empty(rng):
    """Every third node receives no edge, nor do the last 20."""
    ids = np.sort(rng.choice(np.arange(0, 300, 3), 900))
    return ids, 320


def _ragged(rng):
    """1001 edges: no tile size divides them."""
    return np.sort(rng.integers(0, 250, 1001)), 250


SYNTHETIC = {"hub": _hub, "empty": _empty, "ragged": _ragged}
GRAPHCAST = ("mesh_recv", "mesh_send", "g2m_recv", "g2m_send", "m2g_recv", "m2g_send")


@pytest.fixture(scope="module")
def graphcast_ids():
    return _edge_set_ids()


def _ids(case, graphcast_ids):
    rng = np.random.default_rng(sum(map(ord, case)))
    if case in SYNTHETIC:
        return SYNTHETIC[case](rng)
    return graphcast_ids[case]


@pytest.mark.parametrize("case", GRAPHCAST + tuple(SYNTHETIC))
def test_sorted_sum_equals_segment_sum_and_its_gradient(case, graphcast_ids):
    ids, n = _ids(case, graphcast_ids)
    tiles, pairs = (jnp.asarray(a) for a in ops.sorted_layout(ids, n))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((ids.size, D)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((n, D)), jnp.float32)
    jids = jnp.asarray(ids, jnp.int32)

    def plain(x):
        return jax.ops.segment_sum(x, jids, num_segments=n)

    def sorted_(x):
        return ops.sorted_segment_sum(x, tiles, pairs, n)

    _close(jax.jit(sorted_)(x), plain(x))
    # The same cotangent through both: one gather ``w[ids]`` either way.
    vjp = [jax.jit(lambda x, w, f=f: jax.vjp(f, x)[1](w)[0])(x, w) for f in (sorted_, plain)]
    np.testing.assert_array_equal(*vjp)


@pytest.mark.parametrize("case", GRAPHCAST + tuple(SYNTHETIC))
def test_sorted_gather_gradient_equals_the_plain_gathers(case, graphcast_ids):
    """Edges in some other order (shuffled), gathered by ``idx``; the
    gradient goes through ``order`` into the sorted layout."""
    ids, n = _ids(case, graphcast_ids)
    rng = np.random.default_rng(2)
    idx = rng.permutation(ids)
    order = np.argsort(idx, kind="stable")
    layout = [jnp.asarray(a) for a in ops.sorted_layout(idx[order], n)]
    h = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((ids.size, D)), jnp.float32)
    jidx, jorder = jnp.asarray(idx, jnp.int32), jnp.asarray(order, jnp.int32)

    (got, f_got), (want, f_want) = (
        jax.vjp(lambda h: ops.sorted_gather(h, jidx, jorder, *layout), h),
        jax.vjp(lambda h: h[jidx], h))
    np.testing.assert_array_equal(got, want)
    _close(f_got(w)[0], f_want(w)[0])


def test_layout_visits_each_block_with_the_tiles_that_hold_its_edges():
    """Every node block appears, in order, first with flag bit 0; its
    active pairs name exactly the tiles holding its edges; a block with no
    edges has one inactive pair."""
    from repro.kernels.sorted_sum import BLOCK_ROWS

    for ids, n in (_hub(np.random.default_rng(0)), _empty(np.random.default_rng(1))):
        tiles, pairs = ops.sorted_layout(ids, n)
        t = tiles.shape[2]
        assert t == ops.SORTED_TILE and tiles.shape[:2] == (-(-ids.size // t), 1)
        np.testing.assert_array_equal(tiles.reshape(-1)[:ids.size], ids)
        assert np.all(tiles.reshape(-1)[ids.size:] == -2)
        block, tile, flag = pairs
        n_blocks = -(-n // BLOCK_ROWS)
        np.testing.assert_array_equal(np.unique(block), np.arange(n_blocks))
        assert np.all(np.diff(block) >= 0)
        for b in range(n_blocks):
            mine = block == b
            assert flag[mine][0] % 2 == 1 and np.all(flag[mine][1:] % 2 == 0)
            held = set((np.flatnonzero((ids >= b * BLOCK_ROWS) & (ids < (b + 1) * BLOCK_ROWS))
                        // t).tolist())
            active = set(tile[mine & (flag >= 2)].tolist())
            assert active == held and (held or mine.sum() == 1)
    with pytest.raises(ValueError):
        ops.sorted_layout(np.array([3, 1, 2]), 4)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    yield from _eqns(sub.jaxpr)


def test_interaction_gradient_has_no_scatter_and_sums_in_float32():
    """The gradient of one GraphCast interaction step has no scatter; the
    forward sum and the two gathers' transposes are the sorted sum's three
    kernels, and every contraction in them keeps the float32 sum: exact
    bfloat16 parts of the messages against the 0/1 one-hot, accumulated in
    float32, or HIGHEST precision. A float32 operand at default precision,
    which a TPU would round to bfloat16 and a CPU would not, fails."""
    cfg = get_arch("graphcast").make_reduced()
    batch = {k: jnp.asarray(v) for k, v in gc.graphcast_graph(cfg).arrays().items()}
    p = jax.tree_util.tree_map(lambda a: a[0],
                               gc.graphcast_init(jax.random.PRNGKey(0), cfg)["processor"])
    mesh = gc._edge_set(batch, "mesh")
    h = jnp.ones((batch["mesh_nodes"].shape[0], cfg.d_latent))
    e = jnp.ones((batch["mesh_senders"].shape[0], cfg.d_latent))

    def loss(p, e, h):
        e2, h2 = gc._interaction(p, e, h, h, mesh)
        return jnp.sum(e2) + jnp.sum(h2 ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    assert "scatter" not in jax.jit(grad).lower(p, e, h).as_text()
    eqns = list(_eqns(jax.make_jaxpr(grad)(p, e, h).jaxpr))
    assert not any("scatter" in eqn.primitive.name for eqn in eqns)
    kernels = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"]
    assert len(kernels) == 3
    for kernel in kernels:
        dots = [d for d in _eqns(kernel.params["jaxpr"]) if d.primitive.name == "dot_general"]
        assert dots
        for d in dots:
            exact = ({v.aval.dtype for v in d.invars} == {jnp.dtype(jnp.bfloat16)}
                     and d.params["preferred_element_type"] == jnp.float32)
            assert exact or d.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
