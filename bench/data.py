"""The benchmark's inputs: a configuration's dataset, made from its own seed.

The datasets are synthetic stand-ins at the exact node, edge, feature and
label counts of the COIN paper's Table I: a homophilous planted-partition
graph with power-law out-degrees and sparse bag-of-words features. The
generator is the benchmark's own copy of the repository's
`citation_like` (same draws in the same order, so the same seed gives the
same graph), kept here so that neither the program under test nor a later
change to it makes the inputs the reference is compared on.

A public dataset is one fixed graph, so the dataset is drawn from the
configuration's fixed ``dataset.seed``; a run's ``--seed`` draws only the
weights and the traffic.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class RawGraph:
    """A directed graph without self-loops, in its generated node order."""

    n_nodes: int
    senders: np.ndarray          # (E,) int32
    receivers: np.ndarray        # (E,) int32
    features: np.ndarray         # (N, F) float32
    labels: np.ndarray           # (N,) int32

    @property
    def n_edges(self) -> int:
        return int(self.senders.shape[0])


def make_graph(ds: dict) -> RawGraph:
    """The dataset a configuration's ``dataset`` entry describes."""
    if ds["generator"] != "citation_like":
        raise ValueError(f"unknown dataset generator {ds['generator']!r}")
    return citation_like(
        ds["n_nodes"], ds["n_edges"], ds["n_features"], ds["n_labels"],
        homophily=ds["homophily"], alpha=ds["alpha"], feature_nnz=ds["feature_nnz"],
        seed=ds["seed"])


def citation_like(n_nodes: int, n_edges: int, n_features: int, n_labels: int,
                  homophily: float, alpha: float, feature_nnz: int,
                  seed: int) -> RawGraph:
    """Labels in contiguous blocks; each edge's receiver is drawn from the
    sender's label block with probability ``homophily``, else uniformly;
    sender out-degrees follow a power law of exponent ``alpha``. Exactly
    ``n_edges`` directed edges, none a self-loop."""
    rng = np.random.default_rng(seed)
    labels = (np.arange(n_nodes, dtype=np.int64) * n_labels // n_nodes).astype(np.int32)
    block_lo = np.searchsorted(labels, np.arange(n_labels))
    block_hi = np.searchsorted(labels, np.arange(n_labels), side="right")
    ranks = np.arange(1, n_nodes + 1, dtype=np.float64)
    w = ranks ** (-alpha)
    w /= w.sum()
    src_deg = rng.permutation(rng.multinomial(n_edges, w))
    src = np.repeat(np.arange(n_nodes, dtype=np.int64), src_deg)
    same = rng.random(n_edges) < homophily
    lbl = labels[src]
    lo, hi = block_lo[lbl], block_hi[lbl]
    dst_same = lo + (rng.random(n_edges) * (hi - lo)).astype(np.int64)
    dst_rand = rng.integers(0, n_nodes, size=n_edges)
    dst = np.where(same, dst_same, dst_rand)
    loop = dst == src
    dst[loop] = (dst[loop] + 1) % n_nodes
    features = _bow_features(n_nodes, n_features, feature_nnz, labels, rng)
    return RawGraph(n_nodes, src.astype(np.int32), dst.astype(np.int32), features, labels)


def _bow_features(n_nodes: int, n_features: int, nnz: int, labels: np.ndarray,
                  rng: np.random.Generator) -> np.ndarray:
    """Binary bag-of-words rows with a label-correlated slice of columns."""
    x = np.zeros((n_nodes, n_features), dtype=np.float32)
    cols = rng.integers(0, n_features, size=(n_nodes, nnz))
    np.put_along_axis(x, cols, 1.0, axis=1)
    n_labels = int(labels.max()) + 1
    sig = min(8, max(1, n_features // max(n_labels, 1) // 4))
    for c in range(n_labels):
        idx = np.flatnonzero(labels == c)
        lo = (c * sig) % max(n_features - sig, 1)
        mask = rng.random((idx.shape[0], sig)) < 0.75
        x[idx[:, None], np.arange(lo, lo + sig)[None, :]] += mask.astype(np.float32)
    return x
