"""Quantization for weights and activations (paper §V-B, Fig. 7).

COIN stores 4-bit weights/activations in the RRAM crossbars (2 bits/cell,
bit-serial inputs) after verifying on GPU that 4-bit quantization-aware
accuracy is within a few points of fp32. We implement symmetric per-tensor
fake quantization with a straight-through estimator so the same GCN can be
trained/evaluated at 2–32 bits, reproducing the Fig. 7 sweep.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "QuantConfig",
    "PAYLOAD_BITS",
    "fake_quant",
    "kth_largest",
    "quantize_tree",
    "payload_bits",
    "quantize_payload",
    "dequantize_payload",
]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    weight_bits: int = 4
    act_bits: int = 4
    enabled: bool = True
    act_percentile: float | None = 99.9   # clip activation outliers (QAT)

    def replace(self, **kw) -> "QuantConfig":
        return dataclasses.replace(self, **kw)


# Threshold bits settled per counting pass of `kth_largest`. On a TPU v5e at
# nell's f32[65755, 5414], 3 bits (11 passes of 7 counts) took 20.9 ms; 1, 2
# and 4 bits took 58.4, 30.2 and 23.7 ms: up to 7 counts per element a pass
# is bound by its read of the array, beyond that by the counting.
_DIGIT_BITS = 3


def kth_largest(mag: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest element of the non-negative ``mag`` (any shape,
    float32 or narrower): bit for bit ``lax.top_k(mag.ravel(), k)[0][-1]``,
    without its sort.

    For a large ``k`` XLA lowers ``top_k`` to a full sort of (value, index)
    pairs: 1.65 s at nell's f32[65755, 5414] on a TPU v5e, where one read of
    the array takes 1.9 ms. Here every pass is one read. A non-negative
    float32's int32 bit pattern orders as its value (+0, denormals and +inf
    included), so the answer is the largest pattern ``t`` with
    ``count(bits >= t) >= k``. ``t`` is built from the top bit down,
    ``_DIGIT_BITS`` bits per pass: a pass counts the elements at or above
    each candidate digit in one variadic reduce, which XLA fuses with the
    compares into a single read of ``mag``, and the digit is the number of
    candidates whose count reaches ``k`` (counts fall as the candidate
    rises; candidate 0 always reaches it).
    """
    n = mag.size
    assert 1 <= k <= n < 2**31, (k, n)      # int32 counts
    assert mag.dtype.itemsize <= 4, mag.dtype
    bits = jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)
    axes = tuple(range(bits.ndim))

    def add(a, b):
        return tuple(x + y for x, y in zip(a, b))

    t = jnp.int32(0)
    for shift in reversed(range(0, 31, _DIGIT_BITS)):
        n_cand = 2 ** min(_DIGIT_BITS, 31 - shift) - 1
        cand = t | (jnp.arange(1, n_cand + 1, dtype=jnp.int32) << shift)
        hits = tuple((bits >= cand[c]).astype(jnp.int32) for c in range(n_cand))
        counts = jax.lax.reduce(hits, (jnp.int32(0),) * n_cand, add, axes)
        t = t | (jnp.sum(jnp.stack(counts) >= k, dtype=jnp.int32) << shift)
    return jax.lax.bitcast_convert_type(t, jnp.float32).astype(mag.dtype)


def fake_quant(x: jax.Array, bits: int, percentile: float | None = None) -> jax.Array:
    """Symmetric per-tensor fake quantization with a straight-through grad.

    bits ≥ 32 (or ≤ 0) is a no-op (fp32 reference). The scale is amax-based
    by default; ``percentile`` clips the calibration range (e.g. 99.9) — at
    ≤4 bits GCN aggregation outputs have heavy degree-driven outliers and a
    pure-amax scale wastes most of the code points (§V-B reproduction note).
    """
    if bits >= 32 or bits <= 0:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    # The range computation is one device scope: its ops carry
    # ``quant.calibrate`` in their profiler ``tf_op`` (docs/observability.md).
    with jax.named_scope("quant.calibrate"):
        mag = jnp.abs(x)
        if percentile is None:
            amax = jnp.max(mag)
        else:
            # Nearest-rank percentile: the p-th percentile of n magnitudes is
            # the ceil(p·n/100)-th smallest, i.e. the (n − ceil(p·n/100) + 1)-th
            # largest. The old `int(n·(1−p/100))` floored to 0 for any tensor
            # with fewer than 1/(1−p/100) elements, so k=1 == pure amax and a
            # single outlier silently owned the whole calibration range. The
            # statistic carries no gradient, per standard QAT.
            n = mag.size
            k = min(n, max(1, n - math.ceil(percentile / 100.0 * n) + 1))
            amax = kth_largest(jax.lax.stop_gradient(mag), k)
        scale = jax.lax.stop_gradient(jnp.where(amax > 0, amax / qmax, 1.0))
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    # Straight-through estimator: forward q, backward identity.
    return x + jax.lax.stop_gradient(q - x)


def quantize_tree(params: Any, bits: int, percentile: float | None = None) -> Any:
    """Fake-quantize every float leaf of a parameter pytree.

    ``percentile`` reaches every leaf's calibration (it was silently dropped
    before, so tree-level quantization always ran pure-amax).
    """
    def leaf(p):
        if isinstance(p, jax.Array) and jnp.issubdtype(p.dtype, jnp.floating):
            return fake_quant(p, bits, percentile=percentile)
        return p

    return jax.tree_util.tree_map(leaf, params)


# --------------------------------------------------------- halo wire payloads
# Wire formats for the halo exchange (DESIGN.md §8, docs/communication.md
# "Overlapped schedule"): the export block is encoded before the collective
# and decoded on receive, so only the compressed representation crosses the
# inter-chip fabric. Unlike fake_quant (QAT emulation in fp32), these change
# the actual transferred dtype.
PAYLOAD_BITS = {None: 32, "fp32": 32, "bf16": 16, "int8": 8}


def payload_bits(payload: str | None) -> int:
    """Wire bits per element for a halo payload format."""
    try:
        return PAYLOAD_BITS[payload]
    except KeyError:
        raise ValueError(
            f"unknown halo payload {payload!r}; expected one of "
            "None/'fp32', 'bf16', 'int8'"
        ) from None


def quantize_payload(
    x: jax.Array, payload: str | None
) -> tuple[jax.Array, jax.Array | None]:
    """Encode an export block for the wire. Returns ``(wire, scale)``.

    * ``None``/``"fp32"`` — identity, scale None.
    * ``"bf16"``          — bfloat16 cast, scale None (dequant is an upcast).
    * ``"int8"``          — symmetric per-export-block scale (amax/127); the
                            (1, 1) fp32 scale travels alongside the payload so
                            the receiver can decode every sender's block.
    """
    if payload in (None, "fp32") or x.shape[0] == 0:
        return x, None
    if payload == "bf16":
        return x.astype(jnp.bfloat16), None
    if payload == "int8":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q, scale.reshape(1, 1)
    payload_bits(payload)  # raises the canonical error
    raise AssertionError  # pragma: no cover


def dequantize_payload(
    wire: jax.Array, scale: jax.Array | None, dtype=jnp.float32
) -> jax.Array:
    """Decode gathered wire rows back to ``dtype``.

    For int8, ``scale`` holds one row per gathered export block — shape
    (n_blocks, 1) against wire (n_blocks·s, d) — and each block is rescaled
    by its sender's amax/127.
    """
    if scale is None:
        return wire.astype(dtype)
    n_blocks = scale.shape[0]
    rows = wire.shape[0]
    if n_blocks > 1 and rows:
        per = rows // n_blocks
        return (
            wire.astype(dtype).reshape(n_blocks, per, -1) * scale[:, :, None]
        ).reshape(rows, -1)
    return wire.astype(dtype) * scale[0]
