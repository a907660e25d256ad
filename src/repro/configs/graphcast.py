"""graphcast [arXiv:2212.12794]: GraphCast_small, the released 1° model
("resolution 1.0 - pressure levels 13 - mesh 2to5"): latent 512, 16
unshared processor layers, sum aggregation, a 181×360 grid, a refinement-5
multimesh of levels 2–5, 83 predicted channels (5 surface, 6 atmospheric ×
13 levels). Departures from the release: `repro.models.graphcast`."""
from repro.configs.registry import ArchSpec, ShapeSpec
from repro.models.graphcast import GraphCastConfig


def make_config(shape: ShapeSpec | None = None) -> GraphCastConfig:
    del shape                       # one shape: the published grid and mesh
    return GraphCastConfig()


def make_reduced() -> GraphCastConfig:
    """15° grid, refinement-2 multimesh of levels 1–2, latent 32, 2 layers,
    5 channels (2 surface, 1 atmospheric × 3 levels)."""
    return GraphCastConfig(resolution=15.0, mesh_splits=2, mesh_min_level=1, d_latent=32,
                           n_layers=2, surface_weights=(1.0, 0.1), n_atmos_vars=1,
                           pressure_levels=(500, 850, 1000), n_forcings=1, n_static=1)


_FULL = GraphCastConfig()

SPEC = ArchSpec(
    arch_id="graphcast",
    family="gnn",
    source="arXiv:2212.12794",
    make_config=make_config,
    make_reduced=make_reduced,
    shapes={"era5_1deg": ShapeSpec("era5_1deg", "graph", n_nodes=181 * 360,
                                   d_feat=_FULL.d_grid_in, n_out=_FULL.n_vars)},
)
