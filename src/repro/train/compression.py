"""Gradient compression for data-parallel all-reduce (beyond-paper
distributed-optimization tricks; DESIGN.md §3).

Two schemes, both with error feedback (the residual from lossy compression is
carried to the next step so the compressed-SGD iterates track the exact ones):

  * int8 per-tensor quantization — 4× wire-byte reduction,
  * top-k sparsification — k/N wire fraction.

``compressed_psum_mean`` performs the data-parallel mean with int8 *wire*
operands via a manual reduce-scatter (all_to_all) + all_gather under
shard_map, so the compiled HLO's collectives
(`repro.launch.steps.collective_bytes`) carry the 4× reduction (a float
psum after local dequant would not save wire bytes).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "int8_compress",
    "int8_decompress",
    "topk_compress",
    "error_feedback_update",
    "compressed_psum_mean",
]

Tree = Any


def int8_compress(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decompress(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    return q.astype(dtype) * scale


def topk_compress(x: jnp.ndarray, k_fraction: float = 0.01) -> jnp.ndarray:
    """Keep the top-|k| entries (by magnitude), zero the rest (same shape —
    a real system would ship (values, indices); the zeroed tensor is the
    mathematically identical lossy channel for error-feedback analysis)."""
    flat = x.reshape(-1)
    k = max(1, int(flat.shape[0] * k_fraction))
    thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
    return kept.reshape(x.shape)


def error_feedback_update(
    grads: Tree, residual: Tree, compress_fn
) -> tuple[Tree, Tree]:
    """g̃ = C(g + e);  e' = (g + e) − g̃   (Seide et al. 1-bit SGD schema)."""
    def one(g, e):
        target = g + e
        compressed = compress_fn(target)
        return compressed, target - compressed

    pairs = jax.tree_util.tree_map(one, grads, residual)
    comp = jax.tree_util.tree_map(lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    res = jax.tree_util.tree_map(lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return comp, res


def compressed_psum_mean(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Data-parallel mean with int8 wire traffic (call inside shard_map).

    reduce-scatter phase: each device quantizes its shard-chunks to int8 and
    all_to_all's them; local dequant + sum; all_gather (int8 again) returns
    the mean. Wire bytes: 2 × n_elements × 1B vs 2 × n_elements × 4B for the
    fp32 psum — the 4× the roofline's collective term sees.
    """
    # psum of a literal folds to the static axis size on every jax version
    # (jax.lax.axis_size only exists on newer builds).
    n = jax.lax.psum(1, axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    q, scale = int8_compress(chunks)
    # Ship int8 chunks; scales are tiny (one fp32 per device).
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)
    local_sum = jnp.sum(q_t.astype(jnp.float32) * scales[:, None], axis=0) / n
    q2, scale2 = int8_compress(local_sum[None, :])
    gathered = jax.lax.all_gather(q2[0], axis_name, tiled=False)
    scales2 = jax.lax.all_gather(scale2, axis_name)
    full = (gathered.astype(jnp.float32) * scales2[:, None]).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)
