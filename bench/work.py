"""Operations and bytes the GCN's algorithm needs, counted from its shapes.

These are the work of the mathematics, not of an implementation: a layer
``act(Â · H · W + b)`` over ``n`` nodes and ``nnz`` nonzeros of Â (edges plus
self-loops) needs the transform (``2·n·F_in·F_out`` operations) and the
aggregation at the narrower of the two widths (``2·nnz·min(F_in, F_out)``),
and at least reads H, W, b and Â in compressed-row form (a float32 weight and
an int32 column per nonzero, an int32 offset per row) and writes the output.
Padding, tiles and recomputation are not counted, so a roofline share built
on these numbers reads the same whatever implements the layer.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

F32 = 4
I32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def peaks(device_kind: str) -> dict:
    """The peak table's entry for this device; a device not in it is an error."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json") from None


def least_seconds(w: Work, peak: dict) -> float:
    """The least time the chip could take: operations at the bf16 peak or
    bytes at the HBM bandwidth, whichever is longer."""
    return max(w.flops / peak["bf16_flops"], w.bytes / peak["hbm_bytes_per_s"])


def gcn_layer(n: int, nnz: int, f_in: int, f_out: int) -> Work:
    """One forward layer: transform plus aggregation, and its least traffic."""
    flops = 2.0 * n * f_in * f_out + 2.0 * nnz * min(f_in, f_out)
    nbytes = F32 * (n * f_in + f_in * f_out + f_out + n * f_out) + nnz * (F32 + I32) + I32 * (n + 1)
    return Work(flops, nbytes)


def gcn_forward(n: int, nnz: int, dims) -> Work:
    out = Work(0.0, 0.0)
    for f_in, f_out in zip(dims[:-1], dims[1:]):
        out = out + gcn_layer(n, nnz, f_in, f_out)
    return out


def gcn_train_step_flops(n: int, nnz: int, dims) -> float:
    """Model operations of one full-graph training step: the forward, and a
    backward of twice the forward except that layer 0 has no input gradient
    (its backward is the weight gradient and the transposed aggregation).
    The quantization's calibration is not model work and is not counted."""
    total = 0.0
    for i, (f_in, f_out) in enumerate(zip(dims[:-1], dims[1:])):
        transform = 2.0 * n * f_in * f_out
        aggregate = 2.0 * nnz * min(f_in, f_out)
        fwd = transform + aggregate
        bwd = transform + aggregate if i == 0 else 2.0 * fwd
        total += fwd + bwd
    return total
