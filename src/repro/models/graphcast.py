"""GraphCast: grid→mesh encoder, multimesh processor, mesh→grid decoder.

Lam et al., "Learning skillful medium-range global weather forecasting",
arXiv:2212.12794 (Science 2023), as released in
github.com/google-deepmind/graphcast. One step maps the state at t−6h and
t (with forcings and static fields) on a lat-lon grid to the state at t+6h.

* Embedders: one MLP each for the grid nodes (their input channels and
  structural features), the mesh nodes (structural features) and the three
  edge sets (`repro.graph.sphere`'s 4 edge features).
* Grid2Mesh: one interaction network over the bipartite grid→mesh graph:
  edges from [edge, grid sender, mesh receiver], mesh nodes from [node, sum
  of the new incoming edges]; grid nodes, which receive no edge, by an MLP
  of their own latent. Residuals on every update.
* Processor: ``n_layers`` unshared interaction networks on the multimesh,
  each recomputed in the backward pass (`jax.checkpoint`), as GraphCast
  trains, so that only each layer's input latents are kept.
* Mesh2Grid: one interaction network over mesh→grid; then an output MLP
  (no LayerNorm) on the grid latents, added to the state at t.
* Every MLP: one hidden layer of ``d_latent`` with swish, a LayerNorm on
  its output (`repro.nn.layers`). Aggregation is a sum, taken over the
  receiver-sorted edges with no scatter (`repro.graph.ops.sorted_segment_sum`);
  the gathers of sender and receiver latents differentiate into the same
  sums (`repro.graph.ops.sorted_gather`).
* Loss: GraphCast's weighted MSE, ``mean_n a_n Σ_c w_c (pred − target)²``:
  ``a_n`` the grid cell's area over the mean (cos latitude times the
  band's width; the pole cells' caps), ``w_c`` the variable's weight
  (atmospheric 1; surface 1 for 2 m temperature and 0.1 for the others)
  times, for an atmospheric level, its pressure over the mean pressure, over
  the number of levels.

Departures from the release: inputs and targets are taken as already
normalized (no per-variable statistics, no normalized-residual target);
the decoder does not update mesh nodes (nothing reads them); the
icosahedron's orientation and the node features are `repro.graph.sphere`'s.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.ops import sorted_gather, sorted_segment_sum
from repro.graph.sphere import GraphCastGraph, build_graph, graph_sizes, latlon_grid
from repro.nn.layers import layer_norm, mlp_apply, mlp_init

__all__ = ["GraphCastConfig", "graphcast_init", "graphcast_forward", "graphcast_loss",
           "graphcast_graph", "loss_weights", "forward_flops"]

# Recompute each processor layer in the backward pass. A module constant,
# not an option: tests swap it for the identity to compare gradients.
_checkpoint = jax.checkpoint

_LEVELS_13 = (50, 100, 150, 200, 250, 300, 400, 500, 600, 700, 850, 925, 1000)


@dataclasses.dataclass(frozen=True)
class GraphCastConfig:
    """GraphCast_small (1°, 13 pressure levels, multimesh 2to5) by default."""

    resolution: float = 1.0         # lat-lon grid spacing, degrees
    mesh_splits: int = 5            # refinement of the finest mesh: 10·4^5+2 nodes
    mesh_min_level: int = 2         # coarsest level whose edges join the multimesh
    radius_fraction: float = 0.6    # Grid2Mesh radius over the longest finest edge
    d_latent: int = 512
    n_layers: int = 16
    # 2t, 10u, 10v, msl, total_precipitation_6hr
    surface_weights: tuple[float, ...] = (1.0, 0.1, 0.1, 0.1, 0.1)
    n_atmos_vars: int = 6           # z, q, t, u, v, w
    pressure_levels: tuple[int, ...] = _LEVELS_13
    n_input_steps: int = 2          # states at t−6h and t
    n_forcings: int = 5             # TOA radiation, year and day progress sin/cos
    n_static: int = 2               # surface geopotential, land-sea mask

    @property
    def n_vars(self) -> int:
        """Predicted channels: surface variables first, then atmospheric
        variables by variable, then level."""
        return len(self.surface_weights) + self.n_atmos_vars * len(self.pressure_levels)

    @property
    def d_grid_in(self) -> int:
        """Grid input channels: the states (oldest first), the forcings at
        each input time and at the target time, the static fields."""
        return (self.n_input_steps * self.n_vars + (self.n_input_steps + 1) * self.n_forcings
                + self.n_static)

    @property
    def geometry(self) -> tuple:
        return (self.resolution, self.mesh_splits, self.mesh_min_level, self.radius_fraction)


def graphcast_graph(cfg: GraphCastConfig) -> GraphCastGraph:
    return build_graph(*cfg.geometry)


def _block_init(key, d_in: int, d: int) -> dict:
    return {"mlp": mlp_init(key, [d_in, d, d]),
            "ln": {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}}


def _block(p: dict, x):
    return layer_norm(mlp_apply(p["mlp"], x), p["ln"]["g"], p["ln"]["b"])


@functools.partial(jax.jit, static_argnums=1)
def graphcast_init(key: jax.Array, cfg: GraphCastConfig) -> dict:
    d, k = cfg.d_latent, iter(jax.random.split(key, 12))
    layer_keys = jax.random.split(next(k), cfg.n_layers)
    return {
        "embed": {
            "grid": _block_init(next(k), cfg.d_grid_in + 3, d),
            "mesh": _block_init(next(k), 3, d),
            "g2m": _block_init(next(k), 4, d),
            "mesh_edge": _block_init(next(k), 4, d),
            "m2g": _block_init(next(k), 4, d),
        },
        "grid2mesh": {"edge": _block_init(next(k), 3 * d, d),
                      "node": _block_init(next(k), 2 * d, d),      # mesh receivers
                      "grid": _block_init(next(k), d, d)},         # grid senders
        "processor": jax.vmap(lambda kk: {
            "edge": _block_init(jax.random.fold_in(kk, 0), 3 * d, d),
            "node": _block_init(jax.random.fold_in(kk, 1), 2 * d, d)})(layer_keys),
        "mesh2grid": {"edge": _block_init(next(k), 3 * d, d),
                      "node": _block_init(next(k), 2 * d, d)},
        "output": mlp_init(next(k), [d, d, cfg.n_vars]),
    }


def _edge_set(batch: dict, name: str) -> dict:
    """One edge set's indices and sorted-sum layouts (`GraphCastGraph.arrays`)."""
    return {k: batch[f"{name}_{k}"] for k in (
        "senders", "receivers", "send_order", "recv_tiles", "recv_pairs", "send_tiles",
        "send_pairs")}


def _interaction(p: dict, e, h_send, h_recv, g: dict):
    """One interaction network step with residuals; returns the new edge
    and receiver latents. Messages are the new edge latents, summed. The
    sum and the two gathers' gradients are sorted sums over ``g``'s
    layouts (`repro.graph.ops`): no scatter, forward or backward."""
    recv = g["recv_tiles"], g["recv_pairs"]
    sent = sorted_gather(h_send, g["senders"], g["send_order"], g["send_tiles"], g["send_pairs"])
    got = sorted_gather(h_recv, g["receivers"], None, *recv)
    e_new = _block(p["edge"], jnp.concatenate([e, sent, got], axis=-1))
    agg = sorted_segment_sum(e_new, *recv, h_recv.shape[0])
    h_new = _block(p["node"], jnp.concatenate([h_recv, agg], axis=-1))
    return e + e_new, h_recv + h_new


def graphcast_forward(params: dict, batch: dict, cfg: GraphCastConfig) -> jnp.ndarray:
    """The next state on the grid, ``(n_grid, n_vars)``."""
    x = batch["grid_inputs"]
    with jax.named_scope("graphcast.embed"):
        emb = params["embed"]
        h_grid = _block(emb["grid"], jnp.concatenate([x, batch["grid_nodes"]], axis=-1))
        h_mesh = _block(emb["mesh"], batch["mesh_nodes"])
        e_g2m = _block(emb["g2m"], batch["g2m_edges"])
        e_mesh = _block(emb["mesh_edge"], batch["mesh_edges"])
        e_m2g = _block(emb["m2g"], batch["m2g_edges"])

    with jax.named_scope("graphcast.grid2mesh"):
        p = params["grid2mesh"]
        _, h_mesh = _interaction(p, e_g2m, h_grid, h_mesh, _edge_set(batch, "g2m"))
        h_grid = h_grid + _block(p["grid"], h_grid)

    with jax.named_scope("graphcast.processor"):
        mesh = _edge_set(batch, "mesh")

        @_checkpoint
        def layer(carry, p):
            e, h = carry
            return _interaction(p, e, h, h, mesh), None

        (_, h_mesh), _ = jax.lax.scan(layer, (e_mesh, h_mesh), params["processor"])

    with jax.named_scope("graphcast.mesh2grid"):
        _, h_grid = _interaction(params["mesh2grid"], e_m2g, h_mesh, h_grid,
                                 _edge_set(batch, "m2g"))
        out = mlp_apply(params["output"], h_grid)
    state = x[:, (cfg.n_input_steps - 1) * cfg.n_vars: cfg.n_input_steps * cfg.n_vars]
    return state + out


def loss_weights(cfg: GraphCastConfig) -> tuple[np.ndarray, np.ndarray]:
    """(per grid node area weight, mean 1; per channel weight), float32."""
    lat, lon = latlon_grid(cfg.resolution)
    step = np.deg2rad(cfg.resolution)
    w = np.cos(np.deg2rad(lat)) * np.sin(step / 2)
    w[[0, -1]] = np.sin(step / 4) ** 2                  # the polar caps
    area = np.repeat(w / w.mean(), lon.shape[0])
    levels = np.asarray(cfg.pressure_levels, np.float64)
    atmos = np.tile(levels / levels.mean() / levels.shape[0], cfg.n_atmos_vars)
    chan = np.concatenate([np.asarray(cfg.surface_weights, np.float64), atmos])
    return area.astype(np.float32), chan.astype(np.float32)


def graphcast_loss(params: dict, batch: dict, cfg: GraphCastConfig) -> jnp.ndarray:
    area, chan = loss_weights(cfg)
    sq = jnp.square(graphcast_forward(params, batch, cfg) - batch["grid_target"])
    # Elementwise, not a matmul: a TPU would take its operands in bfloat16.
    return jnp.mean(area * jnp.sum(sq * chan, axis=-1))


def forward_flops(cfg: GraphCastConfig) -> float:
    """Matmul operations of one forward pass (2 per multiply-add)."""
    z = dict(graph_sizes(*cfg.geometry))
    d = cfg.d_latent

    def mlp(rows, d_in, d_out=d):
        return 2.0 * rows * (d_in * d + d * d_out)

    embed = (mlp(z["n_grid"], cfg.d_grid_in + 3) + mlp(z["n_mesh"], 3)
             + mlp(z["n_g2m"] + z["n_mesh_edges"] + z["n_m2g"], 4))
    g2m = mlp(z["n_g2m"], 3 * d) + mlp(z["n_mesh"], 2 * d) + mlp(z["n_grid"], d)
    proc = cfg.n_layers * (mlp(z["n_mesh_edges"], 3 * d) + mlp(z["n_mesh"], 2 * d))
    m2g = mlp(z["n_m2g"], 3 * d) + mlp(z["n_grid"], 2 * d) + mlp(z["n_grid"], d, cfg.n_vars)
    return embed + g2m + proc + m2g
