"""The numbers that decide ``correct``, each beside its limit."""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def checks(readings: dict, limits: dict) -> list[Check]:
    """One check per limit; a reading that is missing fails."""
    return [Check(k, float(readings.get(k, math.inf)), float(lim)) for k, lim in limits.items()]


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, the gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's.
    ``keep`` names the leaves that count (all where None)."""
    pn, rn = _norms(prog), _norms(ref)
    names = [k for k in rn if keep is None or k in keep]
    median = float(np.median([rn[k] for k in names])) if names else 0.0
    return {k: abs(pn[k] - rn[k]) / max(rn[k], median) if max(rn[k], median) > 0 else math.inf
            for k in names}


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """The worst leaf's `leaf_gaps`."""
    gaps = leaf_gaps(prog, ref, keep)
    return max(gaps.values()) if gaps else math.inf


def moving_leaves(ref_grads: dict, floor: float = 1e-3) -> set:
    """Leaves whose reference gradient norm is at least ``floor`` times the
    median leaf's: the rest move under Adam by round-off alone."""
    rn = _norms(ref_grads)
    median = float(np.median(list(rn.values())))
    return {k for k, v in rn.items() if v >= floor * median}


def train_readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref`` each hold ``losses`` (first steps), ``grads`` (first
    step, by leaf) and ``change`` (parameters after the steps minus before,
    by leaf)."""
    n = len(ref["losses"])
    if len(prog["losses"]) < n:
        loss_gap = math.inf
    else:
        loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"][:n], ref["losses"]))
    if not all(math.isfinite(x) for x in prog["losses"][:n]):
        loss_gap = math.inf
    return {
        "loss_gap": loss_gap,
        "grad_gap": norm_gap(prog["grads"], ref["grads"]),
        "change_gap": norm_gap(prog["change"], ref["change"], keep=moving_leaves(ref["grads"])),
    }
