"""What the per-layer metric readers share."""

# The program's Pallas kernels, as their ops appear in a TPU trace: Mosaic
# custom calls (their names follow the jitted wrapper, e.g.
# ``jvp_jit_fused_gcn_layer_pallas__``).
KERNELS = r'custom_call_target="tpu_custom_call"'

# The forward fused GCN layer kernel alone (`repro.kernels.fused_gcn`'s
# ``fused_gcn_layer_pallas``; ``jvp_`` where it runs under differentiation),
# by its op's name: not the backward's passes, and no other kernel.
FUSED_FORWARD = r'^%(?:jvp_)?jit_fused_gcn_layer_pallas__(?:\.\d+)? = .*' + KERNELS
