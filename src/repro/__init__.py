"""repro — COIN (communication-aware GCN acceleration) as a multi-pod JAX framework.

Layers:
  repro.core      — the paper's contribution: energy model, optimal-CE solver,
                    graph partitioning, NoC trace model, dataflow chooser,
                    quantization, TPU-retargeted planner.
  repro.graph     — graph substrate (segment-op message passing, BSR blocking,
                    neighbor sampling, synthetic generators).
  repro.nn        — neural-net layers (attention, MoE, norms, embeddings).
  repro.models    — model zoo (GCN + 10 assigned architectures).
  repro.kernels   — Pallas TPU kernels (+ jnp oracles).
  repro.recsys    — embedding-bag / feature-interaction substrate.
  repro.train     — optimizers, loop, checkpointing, compression, elasticity.
  repro.dist      — mesh/sharding utilities and collective helpers.
  repro.configs   — one config per assigned architecture.
  repro.launch    — meshes, GNN step functions and cell builder, train/serve
                    drivers.
"""

__version__ = "1.0.0"
