"""GraphCast (`repro.models.graphcast`) against its plain reference
(`tests/graphcast_reference.py`), and its geometry (`repro.graph.sphere`)
against the invariants of the icosahedral multimesh and the grid↔mesh
graphs. All at reduced sizes on the CPU."""
from __future__ import annotations

import dataclasses

import graphcast_reference as ref
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.graph import sphere
from repro.models import graphcast as gc

CFG = get_arch("graphcast").make_reduced()   # 15° grid, multimesh 1to2, latent 32, 2 layers


def _batch(cfg, seed):
    graph = {k: jnp.asarray(v) for k, v in gc.graphcast_graph(cfg).arrays().items()}
    rng = np.random.default_rng(seed)
    n = graph["grid_nodes"].shape[0]
    return dict(graph,
                grid_inputs=jnp.asarray(rng.standard_normal((n, cfg.d_grid_in)), jnp.float32),
                grid_target=jnp.asarray(rng.standard_normal((n, cfg.n_vars)), jnp.float32))


def _loss_and_grads(loss, params, batch):
    return jax.jit(jax.value_and_grad(loss))(params, batch)


# Compiled once for every seed.
_program = jax.jit(jax.value_and_grad(lambda p, b: gc.graphcast_loss(p, b, CFG)))
_reference = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, CFG)))


def test_reduced_config_is_the_published_architecture_cut_in_size():
    assert (CFG.resolution, CFG.mesh_splits, CFG.mesh_min_level) == (15.0, 2, 1)
    assert (CFG.d_latent, CFG.n_layers, CFG.n_vars) == (32, 2, 5)
    full = get_arch("graphcast").make_config()
    assert (full.d_latent, full.n_layers, full.n_vars, full.d_grid_in) == (512, 16, 83, 183)
    assert (full.resolution, full.mesh_splits, full.mesh_min_level) == (1.0, 5, 2)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_program_matches_the_plain_reference(seed):
    """Loss and every gradient leaf. Both sides are float32 on the CPU
    (full-precision matmuls there) and differ only in summation order
    (segment_sum against scatter-add, a scan against a loop, recomputation):
    a few ulps per op through 4 interaction steps. 1e-4 relative leaves two
    orders of magnitude of room; a skipped layer or dropped edge feature
    moves the loss by percents."""
    params = gc.graphcast_init(jax.random.PRNGKey(seed % 2**31), CFG)
    batch = _batch(CFG, seed)
    got, g_got = _program(params, batch)
    want, g_want = _reference(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(g_want))
    assert len(flat_got) == len(flat_want)
    for path, g in flat_got:
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-4, atol=1e-4 * np.abs(w).max(),
                                   err_msg=jax.tree_util.keystr(path))


def test_reference_sees_a_skipped_layer():
    """The comparison above fails a program that skips a processor layer."""
    params = gc.graphcast_init(jax.random.PRNGKey(3), CFG)
    batch = _batch(CFG, 3)
    skipped = dict(params, processor=jax.tree_util.tree_map(lambda a: a[:1], params["processor"]))
    cut = dataclasses.replace(CFG, n_layers=1)
    got = float(jax.jit(lambda p, b: gc.graphcast_loss(p, b, cut))(skipped, batch))
    want = float(_reference(params, batch)[0])
    assert abs(got - want) / want > 1e-3


def test_checkpointed_gradients_equal_uncheckpointed(monkeypatch):
    """Recomputation lets XLA fuse the forward differently, so float32
    gradients may differ in their last bits (a few 1e-6 relative seen);
    1e-5 allows that and no more."""
    params = gc.graphcast_init(jax.random.PRNGKey(1), CFG)
    batch = _batch(CFG, 1)

    def loss(p, b):
        return gc.graphcast_loss(p, b, CFG)

    l1, g1 = _loss_and_grads(loss, params, batch)
    monkeypatch.setattr(gc, "_checkpoint", lambda f: f)
    l2, g2 = _loss_and_grads(loss, params, batch)
    assert float(l1) == float(l2)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6 * np.abs(np.asarray(b)).max())


# ---------------------------------------------------------------- geometry
@pytest.mark.parametrize("splits", [0, 1, 2, 3])
def test_refinement_counts(splits):
    meshes = sphere.mesh_hierarchy(splits)
    for r, m in enumerate(meshes):
        assert m.vertices.shape == (10 * 4**r + 2, 3)
        assert m.faces.shape == (20 * 4**r, 3)
        np.testing.assert_allclose(np.linalg.norm(m.vertices, axis=1), 1.0, atol=1e-12)
        s, t = sphere.mesh_edges(m.faces)
        assert s.shape[0] == 2 * 30 * 4**r
        # a level's vertices are a prefix of the next level's
        np.testing.assert_array_equal(meshes[-1].vertices[: m.vertices.shape[0]], m.vertices)


@pytest.mark.parametrize("splits,min_level", [(2, 1), (2, 2), (3, 0), (5, 2)])
def test_multimesh_edge_count(splits, min_level):
    s, r = sphere.multimesh_edges(sphere.mesh_hierarchy(splits), min_level)
    assert s.shape[0] == 2 * 30 * sum(4**k for k in range(min_level, splits + 1))
    pairs = np.stack([s, r], 1)
    assert np.unique(pairs, axis=0).shape[0] == pairs.shape[0]          # no edge repeats
    assert not np.any(s == r)


@pytest.mark.parametrize("resolution,splits", [(15.0, 2), (5.0, 3)])
def test_grid_mesh_graphs(resolution, splits):
    g = sphere.build_graph(resolution, splits, 1, 0.6)
    z = g.sizes
    assert z["n_grid"] == (int(180 / resolution) + 1) * int(360 / resolution)
    assert z["n_mesh"] == 10 * 4**splits + 2
    # every grid node sends at least one Grid2Mesh edge
    assert np.bincount(g.g2m_senders, minlength=z["n_grid"]).min() >= 1
    # exactly three Mesh2Grid edges into each grid node, from one triangle
    assert np.all(np.bincount(g.m2g_receivers, minlength=z["n_grid"]) == 3)
    for e in (g.mesh_edges, g.g2m_edges, g.m2g_edges):
        assert e.shape[1] == 4
        assert e[:, 0].max() == pytest.approx(1.0) and e[:, 0].min() > 0
        # the local-frame vector has the edge's length
        np.testing.assert_allclose(np.linalg.norm(e[:, 1:], axis=1), e[:, 0], atol=1e-6)
    for s, r in ((g.mesh_senders, g.mesh_receivers), (g.g2m_senders, g.g2m_receivers),
                 (g.m2g_senders, g.m2g_receivers)):
        assert np.all(np.diff(r) >= 0)                                 # ordered by receiver


def test_radius_query_equals_brute_force_and_triangles_hold_their_points():
    meshes = sphere.mesh_hierarchy(3)
    lat, lon = sphere.latlon_grid(5.0)
    xyz = sphere.latlon_to_xyz(np.repeat(lat, lon.size), np.tile(lon, lat.size))
    v = meshes[-1].vertices
    radius = 0.1
    s, r = sphere.radius_edges(xyz, v, radius, block=97)
    d = np.linalg.norm(xyz[:, None] - v[None], axis=-1)
    gi, mi = np.nonzero(d <= radius)
    assert set(zip(s.tolist(), r.tolist())) == set(zip(gi.tolist(), mi.tolist()))
    faces = meshes[-1].faces[sphere.containing_faces(xyz, meshes)]
    a, b, c = v[faces[:, 0]], v[faces[:, 1]], v[faces[:, 2]]
    for p, q in ((a, b), (b, c), (c, a)):
        assert np.einsum("ij,ij->i", np.cross(p, q), xyz).min() > -1e-12


def test_receiver_lies_at_the_origin_of_its_frame():
    """The receiver's own frame puts it at (1, 0, 0), so a sender due north
    of it has a z component alone there."""
    recv = sphere.latlon_to_xyz(np.array([30.0]), np.array([40.0]))
    send = sphere.latlon_to_xyz(np.array([31.0]), np.array([40.0]))
    f = sphere.edge_features(send, recv, np.array([30.0]), np.array([40.0]))
    assert f[0, 2] == pytest.approx(0.0, abs=1e-6) and f[0, 3] > 0.99


def test_launch_train_trains_graphcast_at_the_reduced_shape():
    from repro.launch import train

    losses = train.main(["--arch", "graphcast", "--steps", "3"])
    assert len(losses) == 3 and np.all(np.isfinite(losses))


def test_dry_run_cell_lowers_data_parallel():
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_cell

    spec = get_arch("graphcast")
    spec = dataclasses.replace(spec, make_config=lambda shape=None: CFG)
    cell = build_cell(spec, spec.shapes["era5_1deg"], make_local_mesh())
    assert cell.abstract_args[2]["grid_inputs"].shape == (1, 312, CFG.d_grid_in)
    assert cell.model_flops == 3.0 * gc.forward_flops(CFG)
    text = cell.lower(make_local_mesh()).as_text()
    assert "gather" in text and "scatter" not in text          # sorted sums, no scatter
