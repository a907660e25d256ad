"""Plain float32 reference of GraphCast's training steps (arXiv:2212.12794).

Straight `jax.numpy`, matmuls at ``highest`` precision, no kernels, no
sharding. It imports
nothing of the program under test. From the program it takes the weights'
values (``--seed``'s draw, by name) and the graphs' connectivity: the index
arrays of the three edge sets and the mesh's vertex positions. It computes
everything else itself: the grid, every node and edge feature, the loss
weights, the forward pass, the loss, the gradients and AdamW.

The processor's layers run as one `jax.lax.scan` over their stacked
weights, each recomputed in the backward pass (`jax.checkpoint`), so that
the 16 layers fit on one chip and compile once; that changes memory and
compile time, not the mathematics.

The model: an MLP is ``linear → swish → linear``, a block an MLP with a
LayerNorm on its output. Embed the grid nodes ([inputs, node features]),
the mesh nodes and the three edge sets with blocks. An interaction step:
new edges = block([edge, sender, receiver]); new receivers = block([receiver,
Σ new edges into it]); both added to the old. One step grid → mesh (and the
grid nodes, which receive nothing, += block(grid)), ``n_layers`` steps on
the multimesh with unshared weights, one step mesh → grid; the prediction
is the state at t plus an MLP of the grid latents. Loss: ``mean over grid
nodes of area × Σ_c w_c (pred − target)²``.

Node features: sin(latitude), cos(longitude), sin(longitude). Edge
features: |s − r| and R(s − r), R the rotation taking the receiver to
latitude 0, longitude 0 (−longitude about z, then +latitude about y), all
over the set's longest |s − r|.

Parameters are a flat dict named by path (``embed/grid/mlp/l0/w``); the
processor's leaves carry a leading layer axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.gcn import REFERENCE, Precision, _cast, adamw_step, matmul

EDGE_SETS = ("mesh", "g2m", "m2g")


def _latlon(xyz: np.ndarray):
    return np.arcsin(np.clip(xyz[:, 2], -1.0, 1.0)), np.arctan2(xyz[:, 1], xyz[:, 0])


def _node_features(lat, lon):
    return np.stack([np.sin(lat), np.cos(lon), np.sin(lon)], -1)


def _edge_features(xs, xr, lat_r, lon_r):
    c, s = np.cos(lon_r), np.sin(lon_r)
    o, z = np.ones_like(c), np.zeros_like(c)
    rz = np.stack([np.stack([c, s, z], -1), np.stack([-s, c, z], -1),
                   np.stack([z, z, o], -1)], -2)                  # about z by −lon
    c, s = np.cos(lat_r), np.sin(lat_r)
    ry = np.stack([np.stack([c, z, s], -1), np.stack([z, o, z], -1),
                   np.stack([-s, z, c], -1)], -2)                 # about y by +lat
    local = np.einsum("eij,ejk,ek->ei", ry, rz, xs - xr)
    length = np.linalg.norm(xs - xr, axis=-1, keepdims=True)
    return np.concatenate([length, local], -1) / length.max()


def loss_weights(model: dict):
    lat = np.deg2rad(np.linspace(-90.0, 90.0, model["n_lat"]))
    d = lat[1] - lat[0]
    w = np.cos(lat) * np.sin(d / 2)
    w[0] = w[-1] = np.sin(d / 4) ** 2
    area = np.repeat(w / w.mean(), model["n_lon"])
    lv = np.asarray(model["pressure_levels"], np.float64)
    chan = list(model["surface_weights"]) + list(lv / lv.mean() / lv.size) * len(model["atmos_vars"])
    return area, np.asarray(chan)


def graph_data(model: dict, mesh_xyz: np.ndarray, edges: dict) -> dict:
    """The reference's graph inputs on the device. ``edges``: for each of
    `EDGE_SETS`, ``(senders, receivers)`` (mesh and g2m senders, m2g
    receivers are grid nodes ``i_lat * n_lon + i_lon``)."""
    lat = np.deg2rad(np.linspace(-90.0, 90.0, model["n_lat"]))
    lon = np.deg2rad(np.arange(model["n_lon"]) * 360.0 / model["n_lon"])
    glat, glon = np.repeat(lat, lon.size), np.tile(lon, lat.size)
    gxyz = np.stack([np.cos(glat) * np.cos(glon), np.cos(glat) * np.sin(glon), np.sin(glat)], -1)
    mlat, mlon = _latlon(mesh_xyz)
    pos = {"grid": (gxyz, glat, glon), "mesh": (mesh_xyz, mlat, mlon)}
    ends = {"mesh": ("mesh", "mesh"), "g2m": ("grid", "mesh"), "m2g": ("mesh", "grid")}
    area, chan = loss_weights(model)
    out = {"grid_nodes": _node_features(glat, glon), "mesh_nodes": _node_features(mlat, mlon),
           "area": area, "chan": chan}
    for name in EDGE_SETS:
        s, r = (np.asarray(a, np.int64) for a in edges[name])
        (xs, _, _), (xr, lat_r, lon_r) = pos[ends[name][0]], pos[ends[name][1]]
        out[f"{name}_edges"] = _edge_features(xs[s], xr[r], lat_r[r], lon_r[r])
        out[f"{name}_senders"], out[f"{name}_receivers"] = s.astype(np.int32), r.astype(np.int32)
    return jax.device_put({k: v.astype(np.float32) if v.dtype == np.float64 else v
                           for k, v in out.items()})


def _mlp(p, name, x, how):
    h = matmul(x, p[f"{name}/l0/w"], how) + p[f"{name}/l0/b"]
    h = h * jax.nn.sigmoid(h)
    return matmul(h, p[f"{name}/l1/w"], how) + p[f"{name}/l1/b"]


def _block(p, name, x, how):
    y = _mlp(p, f"{name}/mlp", x, how)
    mu = y.mean(-1, keepdims=True)
    var = ((y - mu) ** 2).mean(-1, keepdims=True)
    return (y - mu) / jnp.sqrt(var + 1e-5) * p[f"{name}/ln/g"] + p[f"{name}/ln/b"]


def _step(p, name, e, h_send, h_recv, s, r, how):
    e_new = _block(p, f"{name}/edge", jnp.concatenate([e, h_send[s], h_recv[r]], -1), how)
    agg = jnp.zeros((h_recv.shape[0], e_new.shape[1]), e_new.dtype).at[r].add(e_new)
    h_new = _block(p, f"{name}/node", jnp.concatenate([h_recv, agg], -1), how)
    return e + e_new, h_recv + h_new


def forward(p: dict, data: dict, x, model: dict, how=None):
    """The next state on the grid from ``x``, the grid inputs."""
    h_grid = _block(p, "embed/grid", jnp.concatenate([x, data["grid_nodes"]], -1), how)
    h_mesh = _block(p, "embed/mesh", data["mesh_nodes"], how)
    e_mesh = _block(p, "embed/mesh_edge", data["mesh_edges"], how)
    _, h_mesh = _step(p, "grid2mesh", _block(p, "embed/g2m", data["g2m_edges"], how),
                      h_grid, h_mesh, data["g2m_senders"], data["g2m_receivers"], how)
    h_grid = h_grid + _block(p, "grid2mesh/grid", h_grid, how)

    proc = {"/" + k[len("processor/"):]: v for k, v in p.items() if k.startswith("processor/")}

    @jax.checkpoint
    def layer(carry, layer_p):
        e, h = carry
        return _step(layer_p, "", e, h, h, data["mesh_senders"], data["mesh_receivers"], how), None

    (e_mesh, h_mesh), _ = jax.lax.scan(layer, (e_mesh, h_mesh), proc)

    _, h_grid = _step(p, "mesh2grid", _block(p, "embed/m2g", data["m2g_edges"], how),
                      h_mesh, h_grid, data["m2g_senders"], data["m2g_receivers"], how)
    n_vars, t = model["n_vars"], model["n_input_steps"]
    return x[:, (t - 1) * n_vars: t * n_vars] + _mlp(p, "output", h_grid, how)


def loss(p, data, x, y, model, how=None):
    err = (forward(p, data, x, model, how) - y) ** 2
    return (data["area"] * (err * data["chan"]).sum(-1)).mean()


def train(params: dict, data: dict, examples: list, model: dict, opt: dict, steps: int,
          precision: Precision = REFERENCE):
    """``steps`` AdamW steps from ``params``, step i on ``examples[i %
    len]`` (each ``(inputs, target)``). Returns the losses, the first
    step's gradients and the parameters after the last step (host numpy,
    float32)."""
    dtype = precision.storage
    data = _cast(data, dtype)

    @jax.jit
    def step(p, m, v, t, data, x, y):
        value, grads = jax.value_and_grad(loss)(p, data, x, y, model, precision.operands)
        p, m, v = adamw_step(p, m, v, t, grads, opt)
        return p, m, v, value, grads

    p = _cast(jax.device_put(params), dtype)
    m = v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first = [], None
    for i in range(steps):
        x, y = examples[i % len(examples)]
        p, m, v, value, grads = step(p, m, v, jnp.asarray(i + 1, jnp.float32), data,
                                     x.astype(dtype), y.astype(dtype))
        losses.append(float(value))
        if first is None:
            first = {k: np.asarray(g, np.float32) for k, g in grads.items()}
    return losses, first, {k: np.asarray(a, np.float32) for k, a in p.items()}
