"""Operations GraphCast's mathematics needs, counted from its shapes.

Matmul operations only (2 per multiply-add), of every MLP: each is ``d_in
→ d → d_out`` with one hidden layer of the latent width ``d``. Gathers,
concatenations, sums, LayerNorms and swishes are left out, as is the
processor's recomputation in the backward pass: a share of the peak built on
these numbers reads the same whatever implements the step.
"""
from __future__ import annotations


def forward_flops(model: dict, sizes: dict) -> float:
    """One forward pass. ``model``: a configuration's ``model`` entry;
    ``sizes``: n_grid, n_mesh, n_mesh_edges, n_g2m, n_m2g."""
    d = model["d_latent"]

    def mlp(rows: int, d_in: int, d_out: int = d) -> float:
        return 2.0 * rows * (d_in * d + d * d_out)

    g, m = sizes["n_grid"], sizes["n_mesh"]
    e_mesh, e_g2m, e_m2g = sizes["n_mesh_edges"], sizes["n_g2m"], sizes["n_m2g"]
    embed = mlp(g, model["d_grid_in"] + 3) + mlp(m, 3) + mlp(e_mesh + e_g2m + e_m2g, 4)
    grid2mesh = mlp(e_g2m, 3 * d) + mlp(m, 2 * d) + mlp(g, d)
    processor = model["n_layers"] * (mlp(e_mesh, 3 * d) + mlp(m, 2 * d))
    mesh2grid = mlp(e_m2g, 3 * d) + mlp(g, 2 * d) + mlp(g, d, model["n_vars"])
    return embed + grid2mesh + processor + mesh2grid


def train_step_flops(model: dict, sizes: dict) -> float:
    """The forward and a backward of twice it (the gradients of every
    matmul's two operands); the grid inputs' gradient, which nothing needs,
    is counted too."""
    return 3.0 * forward_flops(model, sizes)
