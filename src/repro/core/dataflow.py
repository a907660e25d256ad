"""COIN dataflow: feature-extraction-first matmul reordering (paper §IV-C3).

A GCN layer computes O = A · X · W (A: N×N adjacency, X: N×F features,
W: F×H weights). The multiplication order changes the work:

  aggregation-first   : (A·X)·W  → N·N·F + N·F·H multiplies
  feature-first (COIN): A·(X·W)  → N·F·H + N·N·H multiplies

With H ≪ F (e.g. Nell layer 1: F=5414, H=16) the paper reports a 311×
reduction (2.3·10¹³ → 7.4·10¹⁰). The same reordering carries to the TPU
implementation, where the dense-N² term becomes the E-edge sparse term:

  aggregation-first   : E·F + N·F·H  MACs
  feature-first (COIN): N·F·H + E·H  MACs

For the ``"bsr"`` backend the aggregation is neither dense-N² nor per-edge:
the MXU executes one 128×128 × 128×F matmul per **nonzero block**, padding
tiles skipped by the ragged kernel (DESIGN.md §2). Its cost term is
therefore ``nnz_blocks · B² · F`` — a graph whose communities pack into few
tiles aggregates cheaper than its edge count suggests, and a shuffled graph
pays for every smeared tile. `blocked_multiply_count` models it and
`choose_order(backend="bsr", nnz_blocks=…)` uses it, and a bsr cell's
`model_flops` counts it (`repro.launch.steps`).

This module provides the cost models and the order chooser used by the GCN
layer (`repro.models.gcn`) at trace time.
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "DataflowCost",
    "ExchangeCost",
    "dense_multiply_count",
    "sparse_multiply_count",
    "blocked_multiply_count",
    "exchange_cost",
    "choose_order",
]


@dataclasses.dataclass(frozen=True)
class DataflowCost:
    aggregation_first: float
    feature_first: float

    @property
    def reduction(self) -> float:
        """How many × fewer multiplies feature-first performs."""
        return self.aggregation_first / max(self.feature_first, 1.0)

    @property
    def best(self) -> str:
        return "feature_first" if self.feature_first <= self.aggregation_first else "aggregation_first"


def dense_multiply_count(n_nodes: int, d_in: int, d_out: int) -> DataflowCost:
    """Paper's accounting (§IV-C3): crossbars store A densely (no sparsity)."""
    n = float(n_nodes)
    agg_first = n * n * d_in + n * d_in * d_out
    feat_first = n * d_in * d_out + n * n * d_out
    return DataflowCost(aggregation_first=agg_first, feature_first=feat_first)


def sparse_multiply_count(n_nodes: int, n_edges: int, d_in: int, d_out: int) -> DataflowCost:
    """TPU accounting: aggregation is an E-edge segment-sum / block-SpMM."""
    n, e = float(n_nodes), float(n_edges)
    agg_first = e * d_in + n * d_in * d_out
    feat_first = n * d_in * d_out + e * d_out
    return DataflowCost(aggregation_first=agg_first, feature_first=feat_first)


def blocked_multiply_count(
    n_nodes: int, nnz_blocks: int, d_in: int, d_out: int, block: int = 128
) -> DataflowCost:
    """BSR-backend accounting: aggregation runs one B×B × B×F MXU matmul per
    nonzero 128×128 tile (ragged kernel, padding skipped — DESIGN.md §2), so
    the aggregation term is ``nnz_blocks · B² · F``, not ``E · F``. Locality
    reordering (`repro.graph.structure.locality_block_order`) lowers
    ``nnz_blocks`` and with it this cost — density-awareness the edge-count
    model cannot see.
    """
    n, bb = float(n_nodes), float(nnz_blocks) * float(block) * float(block)
    agg_first = bb * d_in + n * d_in * d_out
    feat_first = n * d_in * d_out + bb * d_out
    return DataflowCost(aggregation_first=agg_first, feature_first=feat_first)


@dataclasses.dataclass(frozen=True)
class ExchangeCost:
    """The halo-exchange wire model of docs/communication.md: per-device
    per-layer rows crossing the fabric, compressed by the payload format and
    hidden behind interior compute.

      wire_bytes    = rows · d · payload_bits / 8        (what crosses)
      exposed_bytes = wire_bytes · (1 − overlap_fraction) (what the critical
                      path still waits on: the overlapped schedule hides a
                      ``overlap_fraction`` share of the exchange behind
                      interior aggregation work)
    """

    rows: int                         # halo rows received per device per layer
    d: int                            # feature width crossing the wire
    payload_bits: int = 32            # fp32 32 | bf16 16 | int8 8
    overlap_fraction: float = 0.0     # HaloPlan.overlap_fraction()

    @property
    def wire_bytes(self) -> float:
        return self.rows * self.d * self.payload_bits / 8.0

    @property
    def exposed_bytes(self) -> float:
        return self.wire_bytes * (1.0 - self.overlap_fraction)

    @property
    def compression(self) -> float:
        """Wire-byte reduction vs the fp32 baseline (32 / payload_bits)."""
        return 32.0 / max(self.payload_bits, 1)


def exchange_cost(
    rows: int, d: int, payload_bits: int = 32, overlap_fraction: float = 0.0
) -> ExchangeCost:
    """Convenience constructor for :class:`ExchangeCost` (the cells'
    ``exchange_accounting`` and the ``choose_order`` exchange term)."""
    return ExchangeCost(
        rows=int(rows), d=int(d), payload_bits=int(payload_bits),
        overlap_fraction=float(overlap_fraction),
    )


def choose_order(
    n_nodes: int, d_in: int, d_out: int, n_edges: int | None = None,
    backend: str = "segment", nnz_blocks: int | None = None, block: int = 128,
    halo_rows: int | None = None, payload_bits: int = 32,
    overlap_fraction: float = 0.0,
) -> str:
    """COIN's rule: run X·W first iff it shrinks the aggregated width.

    For every cost model — dense, per-edge sparse, and the bsr backend's
    per-nonzero-block model (``backend="bsr"`` with ``nnz_blocks``) — the
    comparison reduces to d_out vs d_in (the N·F·H term is shared), so the
    chooser is exact for any accounting; what changes between models is the
    cost *magnitude*.
    Ties go to feature-first (the paper's order).

    ``halo_rows`` adds the exchange term of the sharded halo schedule:
    feature-first exchanges the transformed (d_out-wide) rows and
    aggregation-first the raw (d_in-wide) rows, each scaled by the
    overlap/compression model ``payload_bits/32 · (1 − overlap_fraction)``
    (:class:`ExchangeCost`, in element-equivalents). The term moves with the
    SAME d_out-vs-d_in sign as the compute terms, so the argmax is unchanged
    — it exists to make the magnitudes exchange-aware, not to flip
    decisions.
    """
    if backend == "bsr" and nnz_blocks is not None:
        cost = blocked_multiply_count(n_nodes, nnz_blocks, d_in, d_out, block)
    elif n_edges is not None:
        cost = sparse_multiply_count(n_nodes, n_edges, d_in, d_out)
    else:
        cost = dense_multiply_count(n_nodes, d_in, d_out)
    if halo_rows:
        factor = (payload_bits / 32.0) * (1.0 - overlap_fraction)
        cost = DataflowCost(
            aggregation_first=cost.aggregation_first + halo_rows * d_in * factor,
            feature_first=cost.feature_first + halo_rows * d_out * factor,
        )
    return cost.best
