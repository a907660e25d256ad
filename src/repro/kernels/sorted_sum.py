"""Pallas TPU kernel: the sum of receiver-sorted edge rows into their nodes.

COIN's crossbar map applied to the edge→node incidence matrix. The nodes
are cut into blocks of ``B`` rows (one output tile, resident in VMEM), the
sorted edges into tiles of ``T`` rows; because the edges are sorted, each
node block's edges lie in a run of consecutive tiles. The grid walks a
list of (node block, edge tile) pairs in node-block order:

    out[b·B:(b+1)·B] = Σ_{pairs (b, t)} onehot(b, t) @ x[t·T:(t+1)·T]

where ``onehot(b, t)[i, j] = (ids[t·T + j] == b·B + i)``, built in VMEM
from the tile's ids, and the contraction runs on the MXU. A float32 ``x``
is split exactly into three bfloat16 parts (``x = hi + mid + lo``), each
contracted with the 0/1 one-hot (exact products) into a float32
accumulator: the sum keeps float32 precision, with the three MXU passes
that HIGHEST precision would spend on ``x`` and none on the one-hot.

Layout (built host-side by `repro.graph.ops.sorted_layout`):
    ids   : (n_tiles, 1, T) int32 — each tile's edge ids, −2 past the last
                                     edge (never a node)
    pairs : (3, P) int32          — SCALAR-PREFETCHED: per grid step its node
                                     block, its edge tile, and a flag (bit 0:
                                     the block's first step, which zeroes the
                                     output tile; bit 1: the tile holds edges
                                     of the block). A block with no edges gets
                                     one step that only zeroes it.
    x     : (E, D)                — the edge rows in id order; the last tile
                                     may overhang E, and its rows past E are
                                     masked to 0 in VMEM

Consecutive steps that name the same tile (a tile spanning two blocks) or
the same block keep it in VMEM, so ``x`` is read about once and the output
written once. VMEM: two buffers each of an edge tile and an output tile,
2·(T + B)·D floats (2.5 MB at T = 512, B = 128, D = 512), inside v5e's
default scoped limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sorted_sum_pallas", "BLOCK_ROWS"]

# Node rows per output tile: COIN's 128-row crossbar, the MXU's height.
BLOCK_ROWS = 128


def _split3(x):
    """float32 → three bfloat16 parts whose sum is ``x`` exactly."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _kernel(pairs_ref, ids_ref, x_ref, out_ref, *, n_edges: int):
    p = pl.program_id(0)
    flag = pairs_ref[2, p]
    rows, d = out_ref.shape
    tile = x_ref.shape[0]

    @pl.when(flag % 2 == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(flag >= 2)
    def _accumulate():
        node = pairs_ref[0, p] * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
        onehot = (node == ids_ref[0]).astype(jnp.bfloat16)
        edge = pairs_ref[1, p] * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, d), 0)
        x = jnp.where(edge < n_edges, x_ref[...], 0.0)
        out_ref[...] += sum(jnp.dot(onehot, part, preferred_element_type=jnp.float32)
                            for part in _split3(x))


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def sorted_sum_pallas(x: jax.Array, ids: jax.Array, pairs: jax.Array, num_segments: int,
                      interpret: bool = False) -> jax.Array:
    """``(num_segments, D)`` float32 sums of ``x``'s rows by ``ids`` (see
    the module docstring for the layout)."""
    n_edges, d = x.shape
    _, _, tile = ids.shape
    return pl.pallas_call(
        functools.partial(_kernel, n_edges=n_edges),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pairs.shape[1],),
            in_specs=[pl.BlockSpec((1, 1, tile), lambda p, pairs: (pairs[1, p], 0, 0)),
                      pl.BlockSpec((tile, d), lambda p, pairs: (pairs[1, p], 0))],
            out_specs=pl.BlockSpec((BLOCK_ROWS, d), lambda p, pairs: (pairs[0, p], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=interpret,
    )(pairs, ids, x.astype(jnp.float32))
