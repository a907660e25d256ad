"""Message-passing primitives over edge indices (jax.ops.segment_* based).

This is the system's SpMM layer: aggregation `O = A·Z` expressed as
gather(senders) → weight → segment-reduce(receivers). All functions take a
static ``num_segments`` so they stay shard_map/pjit-friendly. Ghost-padded
edges (receiver == n_nodes) accumulate into an extra row that callers slice
off (see PaddedGraph).

`sorted_segment_sum` and `sorted_gather` are the scatter-free pair for edges
sorted by the index they reduce over, with a layout built once on the host
(`sorted_layout`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import _auto
from repro.kernels.sorted_sum import BLOCK_ROWS, sorted_sum_pallas

__all__ = [
    "aggregate",
    "aggregate_padded",
    "segment_softmax",
    "sym_norm_edge_weights",
    "degrees",
    "multi_aggregate",
    "SORTED_TILE",
    "sorted_layout",
    "sorted_segment_sum",
    "sorted_gather",
]


def degrees(receivers: jnp.ndarray, num_nodes: int) -> jnp.ndarray:
    return jax.ops.segment_sum(
        jnp.ones_like(receivers, dtype=jnp.float32), receivers, num_segments=num_nodes
    )


def sym_norm_edge_weights(
    senders: jnp.ndarray, receivers: jnp.ndarray, num_nodes: int
) -> jnp.ndarray:
    """D^-1/2 Ã D^-1/2 edge weights (Kipf–Welling normalization), in-graph."""
    deg_r = degrees(receivers, num_nodes)
    deg_s = degrees(senders, num_nodes)
    inv_r = jax.lax.rsqrt(jnp.maximum(deg_r, 1.0))
    inv_s = jax.lax.rsqrt(jnp.maximum(deg_s, 1.0))
    return inv_s[senders] * inv_r[receivers]


def aggregate(
    features: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    num_nodes: int,
    edge_weight: jnp.ndarray | None = None,
    reduce: str = "sum",
) -> jnp.ndarray:
    """O[r] = reduce_{(s,r) ∈ E} w_sr · Z[s] — the GCN aggregation stage."""
    msgs = features[senders]
    if edge_weight is not None:
        msgs = msgs * edge_weight[:, None]
    if reduce == "sum":
        return jax.ops.segment_sum(msgs, receivers, num_segments=num_nodes)
    if reduce == "mean":
        total = jax.ops.segment_sum(msgs, receivers, num_segments=num_nodes)
        cnt = degrees(receivers, num_nodes)
        return total / jnp.maximum(cnt, 1.0)[:, None]
    if reduce == "max":
        return jax.ops.segment_max(msgs, receivers, num_segments=num_nodes)
    if reduce == "min":
        return jax.ops.segment_min(msgs, receivers, num_segments=num_nodes)
    raise ValueError(f"unknown reduce: {reduce!r}")


def aggregate_padded(
    features: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    num_nodes: int,
    edge_weight: jnp.ndarray | None = None,
    reduce: str = "sum",
) -> jnp.ndarray:
    """Aggregation when edges are ghost-padded: features has a zero ghost row
    appended, the segment space is num_nodes+1, and the ghost row is dropped."""
    feats = jnp.concatenate([features, jnp.zeros_like(features[:1])], axis=0)
    out = aggregate(feats, senders, receivers, num_nodes + 1, edge_weight, reduce)
    return out[:num_nodes]


def multi_aggregate(
    features: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    num_nodes: int,
    edge_weight: jnp.ndarray | None = None,
) -> dict[str, jnp.ndarray]:
    """PNA-style parallel aggregators computed off shared messages:
    mean / max / min / std (std via E[x²]−E[x]² on the same segments)."""
    msgs = features[senders]
    if edge_weight is not None:
        msgs = msgs * edge_weight[:, None]
    ssum = jax.ops.segment_sum(msgs, receivers, num_segments=num_nodes)
    sqsum = jax.ops.segment_sum(msgs * msgs, receivers, num_segments=num_nodes)
    cnt = jnp.maximum(degrees(receivers, num_nodes), 1.0)[:, None]
    mean = ssum / cnt
    var = jnp.maximum(sqsum / cnt - mean * mean, 0.0)
    smax = jax.ops.segment_max(msgs, receivers, num_segments=num_nodes)
    smin = jax.ops.segment_min(msgs, receivers, num_segments=num_nodes)
    # Empty segments: segment_max/min give -inf/+inf; zero them.
    finite = jnp.isfinite(smax)
    smax = jnp.where(finite, smax, 0.0)
    smin = jnp.where(jnp.isfinite(smin), smin, 0.0)
    return {"mean": mean, "max": smax, "min": smin, "std": jnp.sqrt(var + 1e-8)}


def multi_aggregate_edges(
    messages: jnp.ndarray,
    receivers: jnp.ndarray,
    num_nodes: int,
    edge_mask: jnp.ndarray | None = None,
) -> dict[str, jnp.ndarray]:
    """PNA aggregators over per-edge messages (already gathered/transformed).

    edge_mask: optional (E,) 0/1 validity — masked edges are excluded from
    every statistic (count, mean, std, max, min). Used by the halo comm path,
    whose plan pads edge lists with weight-0 edges (DESIGN.md §8).
    """
    if edge_mask is None:
        msum = messages
        cnt = jnp.maximum(degrees(receivers, num_nodes), 1.0)[:, None]
        mmax = mmin = messages
    else:
        m = edge_mask[:, None]
        msum = messages * m
        cnt = jnp.maximum(
            jax.ops.segment_sum(edge_mask, receivers, num_segments=num_nodes), 1.0
        )[:, None]
        mmax = jnp.where(m > 0, messages, -jnp.inf)
        mmin = jnp.where(m > 0, messages, jnp.inf)
    ssum = jax.ops.segment_sum(msum, receivers, num_segments=num_nodes)
    sqsum = jax.ops.segment_sum(msum * messages, receivers, num_segments=num_nodes)
    mean = ssum / cnt
    var = jnp.maximum(sqsum / cnt - mean * mean, 0.0)
    smax = jax.ops.segment_max(mmax, receivers, num_segments=num_nodes)
    smin = jax.ops.segment_min(mmin, receivers, num_segments=num_nodes)
    smax = jnp.where(jnp.isfinite(smax), smax, 0.0)
    smin = jnp.where(jnp.isfinite(smin), smin, 0.0)
    return {"mean": mean, "max": smax, "min": smin, "std": jnp.sqrt(var + 1e-8)}


@partial(jax.jit, static_argnames=("num_nodes",))
def segment_softmax(
    logits: jnp.ndarray, receivers: jnp.ndarray, num_nodes: int
) -> jnp.ndarray:
    """Numerically-stable per-destination softmax over incoming edges (GAT)."""
    seg_max = jax.ops.segment_max(logits, receivers, num_segments=num_nodes)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = logits - seg_max[receivers]
    expd = jnp.exp(shifted)
    denom = jax.ops.segment_sum(expd, receivers, num_segments=num_nodes)
    return expd / jnp.maximum(denom[receivers], 1e-16)


# Edges per tile of `sorted_segment_sum`: the contraction's depth.
SORTED_TILE = 512


def sorted_layout(ids: np.ndarray, num_segments: int) -> tuple[np.ndarray, np.ndarray]:
    """Host layout of `sorted_segment_sum` over ``ids``, sorted ascending.

    The ``E`` edges are cut into ``n_tiles`` tiles of `SORTED_TILE`
    consecutive edges, the ``num_segments`` nodes into blocks of
    `repro.kernels.sorted_sum.BLOCK_ROWS`. Since the ids are sorted, each
    block's edges lie in a run of consecutive tiles; the sum visits each
    (block, tile) pair of such a run, in block order. Returns, int32:

    * ``tiles`` ``(n_tiles, 1, SORTED_TILE)``: the ids, padded with −2 to
      whole tiles;
    * ``pairs`` ``(3, P)``: per pair its node block, its tile, and a flag:
      1 on a block's first pair, plus 2 where the tile holds edges of the
      block (a block with no edges has one pair, flag 1). ``P`` is read
      from the graph: the blocks plus the tiles that cross from one block
      into the next, about ``n_blocks + n_tiles``.

    No window height needs sizing: each block's window is its own 128
    rows, and how many tiles a block reads follows from the ids. Every
    block of ``num_segments`` rows gets a pair, so the sum writes each row.
    """
    ids = np.asarray(ids, np.int64)
    if ids.ndim != 1 or np.any(np.diff(ids) < 0):
        raise ValueError("sorted_layout needs a 1-d array of ids in ascending order")
    if ids.size and (ids[0] < 0 or ids[-1] >= num_segments):
        raise ValueError(f"ids must lie in [0, {num_segments})")
    n_tiles = max(-(-ids.size // SORTED_TILE), 1)
    tiles = np.full(n_tiles * SORTED_TILE, -2, np.int64)
    tiles[:ids.size] = ids

    n_blocks = -(-num_segments // BLOCK_ROWS)
    lo = np.searchsorted(ids, np.arange(n_blocks) * BLOCK_ROWS)
    hi = np.searchsorted(ids, np.arange(1, n_blocks + 1) * BLOCK_ROWS)
    has = hi > lo
    count = np.where(has, (hi - 1) // SORTED_TILE - lo // SORTED_TILE + 1, 1)
    step = np.arange(count.sum()) - np.repeat(np.cumsum(count) - count, count)
    tile = np.minimum(np.repeat(lo // SORTED_TILE, count) + step, n_tiles - 1)
    flag = (step == 0) + 2 * np.repeat(has, count)
    pairs = np.stack([np.repeat(np.arange(n_blocks), count), tile, flag])
    return tiles.reshape(n_tiles, 1, SORTED_TILE).astype(np.int32), pairs.astype(np.int32)


def _sorted_sum(x, tiles, pairs, num_segments):
    if tiles.shape[0] != max(-(-x.shape[0] // tiles.shape[2]), 1):
        raise ValueError(f"a layout of {tiles.shape[0]} tiles for {x.shape[0]} edges")
    with jax.named_scope("graph.sorted_segment_sum"):
        out = sorted_sum_pallas(x, tiles, pairs, num_segments, interpret=_auto(None))
    return out.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def sorted_segment_sum(x, tiles, pairs, num_segments: int):
    """``segment_sum(x, ids, num_segments)`` for ``ids`` sorted ascending,
    with no scatter; ``(tiles, pairs)`` is `sorted_layout(ids,
    num_segments)`.

    Each 128-row block of nodes is summed in VMEM from the edge tiles that
    hold its edges, each tile contracted on the MXU with its one-hot
    incidence block (`repro.kernels.sorted_sum`). The sum is float32
    whatever the device's matmul default: ``x`` enters the MXU as three
    exact bfloat16 parts with float32 accumulation, what
    ``Precision.HIGHEST`` spends on it. The gradient is the gather
    ``g[ids]``.
    """
    return _sorted_sum(x, tiles, pairs, num_segments)


def _sorted_sum_fwd(x, tiles, pairs, num_segments):
    return _sorted_sum(x, tiles, pairs, num_segments), (tiles, x.shape[0])


def _sorted_sum_bwd(num_segments, res, g):
    tiles, n_edges = res
    return g[tiles.reshape(-1)[:n_edges]], None, None


sorted_segment_sum.defvjp(_sorted_sum_fwd, _sorted_sum_bwd)


@jax.custom_vjp
def sorted_gather(h, idx, order, tiles, pairs):
    """``h[idx]``, whose gradient is a `sorted_segment_sum` and no scatter.

    ``order`` puts the edges in ascending ``idx`` (``None`` where they are
    so already); ``(tiles, pairs)`` is `sorted_layout(idx[order],
    h.shape[0])`. The gradient permutes the edges' cotangent into that
    order (a gather) and sums it into ``h``'s rows.
    """
    return h[idx]


def _sorted_gather_fwd(h, idx, order, tiles, pairs):
    return h[idx], (order, tiles, pairs, h.shape[0])


def _sorted_gather_bwd(res, g):
    order, tiles, pairs, n = res
    if order is not None:
        g = g[order]
    return sorted_segment_sum(g, tiles, pairs, n), None, None, None, None


sorted_gather.defvjp(_sorted_gather_fwd, _sorted_gather_bwd)
