"""Plain float32 reference of GraphCast's forward pass and weighted MSE.

Straight `jax.numpy` under ``jax.default_matmul_precision("highest")``: no
kernels, no sharding policy, no scan, no checkpointing, nothing of
`repro.models` or `repro.nn`. It reads the program's parameter tree (the
interface both sides share) and the program's batch: the graphs and their
features come from `repro.graph.sphere`, whose invariants
`tests/test_graphcast.py` checks on their own.

The model (Lam et al., arXiv:2212.12794): embed grid nodes, mesh nodes and
the three edge sets with MLP + LayerNorm; one interaction network grid →
mesh (grid nodes updated by an MLP of their own), ``n_layers`` on the
multimesh, one mesh → grid; an output MLP added to the state at t. An
interaction network: new edges = LN(MLP([edge, sender, receiver])),
new receivers = LN(MLP([receiver, Σ new edges])), both added to the old.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _mlp(p, x):
    h = x @ p["l0"]["w"] + p["l0"]["b"]
    h = h * jax.nn.sigmoid(h)                       # swish
    return h @ p["l1"]["w"] + p["l1"]["b"]


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _block(p, x):
    return _ln(_mlp(p["mlp"], x), p["ln"]["g"], p["ln"]["b"])


def _step(p, e, h_send, h_recv, s, r):
    e_new = _block(p["edge"], jnp.concatenate([e, h_send[s], h_recv[r]], -1))
    agg = jnp.zeros((h_recv.shape[0], e_new.shape[1]), e_new.dtype).at[r].add(e_new)
    return e + e_new, h_recv + _block(p["node"], jnp.concatenate([h_recv, agg], -1))


def forward(params, batch, n_vars: int, n_input_steps: int):
    x = batch["grid_inputs"]
    emb = params["embed"]
    h_grid = _block(emb["grid"], jnp.concatenate([x, batch["grid_nodes"]], -1))
    h_mesh = _block(emb["mesh"], batch["mesh_nodes"])
    e_mesh = _block(emb["mesh_edge"], batch["mesh_edges"])

    p = params["grid2mesh"]
    _, h_mesh = _step(p, _block(emb["g2m"], batch["g2m_edges"]), h_grid, h_mesh,
                      batch["g2m_senders"], batch["g2m_receivers"])
    h_grid = h_grid + _block(p["grid"], h_grid)

    n_layers = params["processor"]["edge"]["ln"]["g"].shape[0]
    for i in range(n_layers):
        layer = jax.tree_util.tree_map(lambda a: a[i], params["processor"])
        e_mesh, h_mesh = _step(layer, e_mesh, h_mesh, h_mesh,
                               batch["mesh_senders"], batch["mesh_receivers"])

    _, h_grid = _step(params["mesh2grid"], _block(emb["m2g"], batch["m2g_edges"]), h_mesh,
                      h_grid, batch["m2g_senders"], batch["m2g_receivers"])
    state = x[:, (n_input_steps - 1) * n_vars: n_input_steps * n_vars]
    return state + _mlp(params["output"], h_grid)


def weights(lat_deg: np.ndarray, n_lon: int, surface_weights, n_atmos: int, levels):
    """Per grid node: the cell's area over the mean (cos latitude times
    sin of half the spacing; a pole's cap sin² of a quarter of it). Per
    channel: the surface weights, then per atmospheric variable each
    level's pressure over the levels' mean and over their count."""
    d = np.deg2rad(lat_deg[1] - lat_deg[0])
    w = np.cos(np.deg2rad(lat_deg)) * np.sin(d / 2)
    w[0] = w[-1] = np.sin(d / 4) ** 2
    area = np.repeat(w / w.mean(), n_lon)
    lv = np.asarray(levels, np.float64)
    chan = list(surface_weights) + list(lv / lv.mean() / lv.size) * n_atmos
    return jnp.asarray(area, jnp.float32), jnp.asarray(chan, jnp.float32)


def loss(params, batch, cfg):
    """GraphCast's weighted MSE; ``cfg`` a `GraphCastConfig` read for its
    sizes only."""
    lat = np.linspace(-90.0, 90.0, int(round(180 / cfg.resolution)) + 1)
    area, chan = weights(lat, int(round(360 / cfg.resolution)), cfg.surface_weights,
                         cfg.n_atmos_vars, cfg.pressure_levels)
    with jax.default_matmul_precision("highest"):
        pred = forward(params, batch, cfg.n_vars, cfg.n_input_steps)
        err = (pred - batch["grid_target"]) ** 2
        return (err * chan[None, :] * area[:, None]).sum() / err.shape[0]
