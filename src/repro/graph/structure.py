"""Graph containers: host-side data, padded static-shape device form, and the
128×128 blocked (BSR) adjacency that mirrors COIN's crossbar mapping.

COIN stores the adjacency in 128×128 RRAM crossbars; the TPU-native analogue
is a block-sparse matrix whose nonzero 128×128 blocks are dense MXU tiles
(DESIGN.md §2, docs/kernels.md). `blocked_adjacency` produces that
representation (numpy, host-side, one-time cost), consumed by
`repro.kernels.bsr_spmm` and `repro.kernels.fused_gcn`. The layout is
**ragged**: the rectangular `(R, T)` tile tables are padded to the max
block-row degree T, but `row_nnzb` records each block-row's true tile count
so the kernel can skip the padding (power-law hub rows stop taxing every
other row). `locality_block_order` computes the COIN CE-mapping / I-GCN
islandization node permutation that densifies blocks before blocking.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = [
    "GraphData",
    "PaddedGraph",
    "to_padded",
    "BlockedAdjacency",
    "blocked_adjacency",
    "blocked_stats",
    "locality_block_order",
    "permute_edge_index",
    "relocate_rows",
    "restore_rows",
]


@dataclasses.dataclass
class GraphData:
    """Host-side (numpy) graph with optional features/labels/positions."""

    n_nodes: int
    edge_index: np.ndarray                  # (2, E) int32, [senders; receivers]
    edge_weight: np.ndarray | None = None   # (E,) float32
    features: np.ndarray | None = None      # (N, F) float32
    labels: np.ndarray | None = None        # (N,) int32
    positions: np.ndarray | None = None     # (N, 3) float32 (geometric models)

    @property
    def n_edges(self) -> int:
        return int(self.edge_index.shape[1])

    def with_self_loops(self) -> "GraphData":
        loops = np.arange(self.n_nodes, dtype=self.edge_index.dtype)
        ei = np.concatenate([self.edge_index, np.stack([loops, loops])], axis=1)
        ew = None
        if self.edge_weight is not None:
            ew = np.concatenate([self.edge_weight, np.ones(self.n_nodes, np.float32)])
        return dataclasses.replace(self, edge_index=ei, edge_weight=ew)

    def symmetrized(self) -> "GraphData":
        rev = self.edge_index[::-1]
        ei = np.concatenate([self.edge_index, rev], axis=1)
        ei = np.unique(ei, axis=1)
        return dataclasses.replace(self, edge_index=ei.astype(np.int32), edge_weight=None)

    def sym_normalized_weights(self) -> np.ndarray:
        """D^-1/2 Ã D^-1/2 weights (Kipf–Welling; the paper's GCN [11])."""
        s, r = self.edge_index
        deg = np.bincount(r, minlength=self.n_nodes).astype(np.float64)
        deg_s = np.bincount(s, minlength=self.n_nodes).astype(np.float64)
        inv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
        inv_s = 1.0 / np.sqrt(np.maximum(deg_s, 1.0))
        return (inv_s[s] * inv[r]).astype(np.float32)


@dataclasses.dataclass
class PaddedGraph:
    """Static-shape device form: edges padded with a ghost node (id = n_nodes).

    Ghost-targeted messages land in segment id `n_nodes` and are sliced off,
    so no mask multiply is needed in the hot loop.
    """

    senders: jnp.ndarray        # (E_pad,) int32
    receivers: jnp.ndarray      # (E_pad,) int32
    edge_weight: jnp.ndarray    # (E_pad,) float32; 0 at padding
    n_nodes: int                # static
    n_real_edges: int           # static

    @property
    def n_edges_padded(self) -> int:
        return int(self.senders.shape[0])


def to_padded(g: GraphData, pad_to: int | None = None, weights: np.ndarray | None = None) -> PaddedGraph:
    e = g.n_edges
    pad_to = pad_to or e
    assert pad_to >= e, "pad_to smaller than edge count"
    if weights is None:
        weights = g.edge_weight if g.edge_weight is not None else np.ones(e, np.float32)
    s = np.full(pad_to, g.n_nodes, np.int32)
    r = np.full(pad_to, g.n_nodes, np.int32)
    w = np.zeros(pad_to, np.float32)
    s[:e], r[:e], w[:e] = g.edge_index[0], g.edge_index[1], weights
    return PaddedGraph(
        senders=jnp.asarray(s),
        receivers=jnp.asarray(r),
        edge_weight=jnp.asarray(w),
        n_nodes=g.n_nodes,
        n_real_edges=e,
    )


@dataclasses.dataclass
class BlockedAdjacency:
    """Ragged BSR-like 128×128 blocking of A (COIN crossbar map → MXU tiles).

    Per block-row, the nonzero block-columns are padded to the max row degree
    so the Pallas kernel can scalar-prefetch a rectangular index array — but
    the true per-row tile count rides along as ``row_nnzb`` (the ragged
    lengths), so `repro.kernels.bsr_spmm` skips the padding tiles entirely
    instead of multiplying zeros:

      block_vals : (n_block_rows, max_nnzb, B, B) float32 — dense tiles
      block_cols : (n_block_rows, max_nnzb) int32 — column-block ids,
                   padding repeats the last valid id with a zero tile
      row_nnzb   : (n_block_rows,) int32 — valid tiles per block-row
                   (the scalar-prefetched ragged lengths; ≤ max_nnzb)

    The matrix may be **rectangular**: rows span ``n_nodes`` receiver nodes
    and columns span ``n_col_nodes`` sender rows (== n_nodes for the global
    square adjacency; == n_local + halo rows for the per-shard halo-path
    blocking of `repro.dist.halo.plan_blocked_adjacency`).
    """

    block_vals: np.ndarray
    block_cols: np.ndarray
    row_nnzb: np.ndarray
    n_nodes: int
    block: int
    n_col_nodes: int = 0              # 0 (legacy) ⇒ square: == n_nodes

    def __post_init__(self):
        if not self.n_col_nodes:
            self.n_col_nodes = self.n_nodes

    @property
    def n_block_rows(self) -> int:
        return int(self.block_vals.shape[0])

    @property
    def n_block_cols(self) -> int:
        return -(-self.n_col_nodes // self.block)

    @property
    def max_nnzb(self) -> int:
        """The rectangular tile-table width T (global max block-row degree)."""
        return int(self.block_vals.shape[1])

    @property
    def n_padded(self) -> int:
        """Row count of the kernel output (block-row grid × B)."""
        return self.n_block_rows * self.block

    @property
    def n_col_padded(self) -> int:
        """Row count the dense feature operand must be padded to."""
        return self.n_block_cols * self.block

    @property
    def nnz_blocks(self) -> int:
        """Total nonzero (materialized, non-padding) 128×128 tiles."""
        return int(self.row_nnzb.sum())

    @property
    def padded_tile_fraction(self) -> float:
        """Fraction of the rectangular (R, T) tile table that is padding —
        the work a dense-T kernel wastes and the ragged kernel skips."""
        grid = self.n_block_rows * self.max_nnzb
        return 1.0 - self.nnz_blocks / max(grid, 1)

    @property
    def density(self) -> float:
        """Fraction of the full R×C block grid that is materialized."""
        grid = self.n_block_rows * self.n_block_cols
        return float(self.nnz_blocks) / max(grid, 1)

    def arrays(self) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(vals, cols, lens) as device arrays — the kernel operand triple."""
        return (
            jnp.asarray(self.block_vals),
            jnp.asarray(self.block_cols),
            jnp.asarray(self.row_nnzb),
        )


def blocked_adjacency(
    n_nodes: int,
    edge_index: np.ndarray,
    edge_weight: np.ndarray | None = None,
    block: int = 128,
    n_col_nodes: int | None = None,
) -> BlockedAdjacency:
    """Build the ragged 128×128 blocked adjacency (numpy, one-time host cost).

    A[r, c] = w for each edge (sender=c, receiver=r): aggregation computes
    O = A·Z, rows = receivers. ``n_col_nodes`` widens the column space past
    ``n_nodes`` for rectangular matrices (halo path: senders index the
    ``[local ‖ halo]`` table, receivers the local block); senders must be
    ``< n_col_nodes`` and receivers ``< n_nodes``.
    """
    s = np.asarray(edge_index[0], dtype=np.int64)
    r = np.asarray(edge_index[1], dtype=np.int64)
    w = (
        np.ones(s.shape[0], np.float32)
        if edge_weight is None
        else np.asarray(edge_weight, np.float32)
    )
    n_cols = n_nodes if n_col_nodes is None else int(n_col_nodes)
    nbr = -(-n_nodes // block)   # ceil: receiver block-rows
    nbc = -(-n_cols // block)    # ceil: sender block-cols
    br, bc = r // block, s // block
    # Unique nonzero blocks, then scatter edges into dense tiles.
    key = br * nbc + bc
    uniq, inv = np.unique(key, return_inverse=True)
    n_blocks = uniq.shape[0]
    vals = np.zeros((n_blocks, block, block), np.float32)
    np.add.at(vals, (inv, r % block, s % block), w)
    ubr, ubc = uniq // nbc, uniq % nbc
    # Group blocks by block-row, pad to max row nnzb (the ragged lengths).
    row_nnzb = np.bincount(ubr, minlength=nbr).astype(np.int32)
    max_nnzb = max(int(row_nnzb.max(initial=1)), 1)
    block_vals = np.zeros((nbr, max_nnzb, block, block), np.float32)
    block_cols = np.zeros((nbr, max_nnzb), np.int32)
    order = np.argsort(ubr, kind="stable")
    pos = np.zeros(nbr, np.int64)
    for idx in order:
        rr = ubr[idx]
        block_vals[rr, pos[rr]] = vals[idx]
        block_cols[rr, pos[rr]] = ubc[idx]
        pos[rr] += 1
    # Pad columns repeat the last valid id (zero tiles; the ragged kernel
    # never touches them, the dense-T ref multiplies harmless zeros).
    for rr in range(nbr):
        if 0 < pos[rr] < max_nnzb:
            block_cols[rr, pos[rr]:] = block_cols[rr, pos[rr] - 1]
    return BlockedAdjacency(
        block_vals=block_vals,
        block_cols=block_cols,
        row_nnzb=row_nnzb,
        n_nodes=n_nodes,
        block=block,
        n_col_nodes=n_cols,
    )


def blocked_stats(
    n_nodes: int,
    edge_index: np.ndarray,
    block: int = 128,
    n_col_nodes: int | None = None,
) -> dict:
    """Blocked-layout statistics WITHOUT materializing any (B, B) tile.

    O(E) integer work — usable at ogbn-products scale where the dense tiles
    of :func:`blocked_adjacency` would not fit. Returns the layout record
    the benchmarks report: ``n_block_rows`` (R),
    ``max_nnzb`` (T, the dense-T pad), ``nnz_blocks`` (tiles the ragged
    kernel executes), ``dense_tiles`` (R·T, tiles a dense-T kernel
    executes), and ``padded_tile_fraction`` (the dense-T waste the ragged
    lengths skip).
    """
    s = np.asarray(edge_index[0], dtype=np.int64)
    r = np.asarray(edge_index[1], dtype=np.int64)
    n_cols = n_nodes if n_col_nodes is None else int(n_col_nodes)
    nbr = -(-n_nodes // block)
    nbc = -(-n_cols // block)
    uniq = np.unique((r // block) * nbc + (s // block))
    row_nnzb = np.bincount(uniq // nbc, minlength=nbr)
    T = max(int(row_nnzb.max(initial=1)), 1)
    nnz = int(row_nnzb.sum())
    return {
        "block": block,
        "n_block_rows": nbr,
        "n_block_cols": nbc,
        "max_nnzb": T,
        "nnz_blocks": nnz,
        "dense_tiles": nbr * T,
        "padded_tile_fraction": 1.0 - nnz / max(nbr * T, 1),
    }


# ======================================================= locality reordering
def locality_block_order(
    n_nodes: int,
    edge_index: np.ndarray,
    block: int = 128,
    method: str = "bfs",
    seed: int = 0,
    refine: bool = True,
) -> np.ndarray:
    """COIN CE-mapping / I-GCN islandization permutation for dense blocking.

    Returns ``perm`` (new position → original node id) — a node order under
    which a community's edges land in few 128×128 tiles instead of smearing
    across the whole block grid. Apply it with :func:`relocate_rows` /
    :func:`permute_edge_index` before :func:`blocked_adjacency` and undo
    outputs with :func:`restore_rows` (round-trip pinned by the hypothesis
    test in `tests/test_kernels.py`).

    method="bfs" (default) — `repro.core.partition.bfs_traversal_order`:
    parent-ordered BFS islandization. On shuffled planted-partition graphs
    it cuts nonzero tiles 3–6× (measured at or beyond the planted ordering
    itself — children pack under their discoverer).
    method="partition" — `repro.core.partition.partition_graph` into
    ``ceil(n_nodes / block)`` parts (BFS region growing, optional ``refine``
    boundary passes, balance-capped — COIN's balanced CE map) laid out
    contiguously; weaker blocks than the traversal but exactly the
    partitioner the halo layer uses.

    On graphs whose node ids are arbitrary (every real-world dataset), both
    measurably cut ``nnz_blocks`` and the dense-T executed-tile count — the
    numbers `benchmarks/kernel_bench.py` records in BENCH_kernels.json.
    """
    from repro.core.partition import bfs_traversal_order, partition_graph

    if n_nodes <= block:
        return np.arange(n_nodes, dtype=np.int64)
    if method == "bfs":
        return bfs_traversal_order(n_nodes, edge_index[0], edge_index[1])
    if method != "partition":
        raise ValueError(f"unknown locality method: {method!r} (bfs | partition)")
    k = -(-n_nodes // block)
    part = partition_graph(n_nodes, edge_index, k, method="bfs", seed=seed, refine=refine)
    return np.argsort(part.assignment, kind="stable").astype(np.int64)


def permute_edge_index(perm: np.ndarray, edge_index: np.ndarray) -> np.ndarray:
    """Rewrite edge endpoints into the permuted node order (perm: new → old)."""
    inv = np.empty(perm.shape[0], np.int64)
    inv[perm] = np.arange(perm.shape[0], dtype=np.int64)
    return inv[np.asarray(edge_index, dtype=np.int64)].astype(np.int32)


def relocate_rows(perm: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Gather per-node rows into the permuted order (row i ← old row perm[i])."""
    return np.asarray(x)[perm]


def restore_rows(perm: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Inverse of :func:`relocate_rows`: scatter permuted rows back to the
    original node order (accepts trailing feature axes)."""
    x = np.asarray(x)
    out = np.empty_like(x)
    out[perm] = x
    return out
