"""Pallas TPU kernels for the perf-critical compute layers.

  bsr_spmm        — ragged 128×128 block-sparse Ã·Z (COIN crossbar → MXU
                    mapping; scalar-prefetched per-block-row lengths skip
                    padding tiles)
  fused_gcn_layer — one whole GCN layer act(Ã·(X·W) + b) in a single
                    pallas_call (fp32 accumulation, optional bf16 operands)
  fm_interaction  — DeepFM linearized second-order interaction
  flash_attention — causal/sliding-window online-softmax attention
  sorted_sum      — sums of receiver-sorted edge rows into 128-row node
                    blocks (one-hot tiles on the MXU; `repro.graph.ops`
                    `sorted_segment_sum` wraps it)

Each kernel ships with a pure-jnp oracle in `ref.py` and a jit'd public
wrapper in `ops.py` (interpret mode on CPU, native on TPU). The kernel
guide is docs/kernels.md.
"""

from repro.kernels.ops import bsr_spmm, fused_gcn_layer, fm_interaction, flash_attention

__all__ = ["bsr_spmm", "fused_gcn_layer", "fm_interaction", "flash_attention"]
