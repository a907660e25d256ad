"""Plain float32 reference of the COIN GCN's full-graph training steps.

Straight `jax.numpy`, matmuls at ``highest`` precision, no kernels, no
blocking, no cache. It imports nothing of the program under test and takes
nothing it made: the weights come from :func:`init_params` (which the
benchmark also hands to the program), the graph from `bench.data`.

The model (Kipf & Welling, arXiv:1609.02907, with COIN's 4-bit quantization,
arXiv:2205.07311 §V-B): each layer computes ``act(Â · fq(H) · fq(W) + b)``
with Â the symmetric-normalized adjacency with self-loops, ``fq`` symmetric
per-tensor fake quantization with a straight-through gradient (weights
scaled by their largest magnitude, activations by the nearest-rank
``act_percentile`` of theirs), ReLU between layers, and cross-entropy over
the labelled nodes. Optimizer: AdamW.

A `Precision` other than `REFERENCE` computes the same mathematics in a
lower precision: the control of the comparison that decides ``correct``
(`CONTROLS`; `bench.calibrate` reads them).
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Precision(NamedTuple):
    """``storage``: the dtype of every array and every elementwise and
    reduction step; the optimizer's constants and bias corrections stay
    float32 scalars and each update is rounded to ``storage``. ``operands``:
    None, or ``"fp8"``: every matmul operand rounded to float8 e4m3's 3-bit
    mantissa (scale-free), with a straight-through gradient."""

    storage: Any = jnp.float32
    operands: str | None = None


REFERENCE = Precision()
# The control: every precision the configurations state taken one step down.
# They keep arrays and accumulation in float32 (-> bfloat16) and, through the
# TPU's default matmul precision, take matmul operands in bfloat16 (-> fp8).
CONTROL = "bf16_fp8"
CONTROLS = {
    "bf16": Precision(jnp.bfloat16, None),
    "bf16_fp8": Precision(jnp.bfloat16, "fp8"),
}


def init_params(key, layer_dims) -> dict:
    """Glorot-normal weights and zero biases, float32, in one jitted call."""

    @functools.partial(jax.jit, static_argnums=1)
    def make(key, dims):
        keys = jax.random.split(key, len(dims) - 1)
        out = {}
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            std = (2.0 / (d_in + d_out)) ** 0.5
            out[f"w{i}"] = jax.random.normal(keys[i], (d_in, d_out), jnp.float32) * std
            out[f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
        return out

    return make(key, tuple(int(d) for d in layer_dims))


def _operand(x, how):
    if how is None:
        return x
    if how != "fp8":
        raise ValueError(f"unknown operand precision {how!r}")
    m, e = jnp.frexp(x)                          # x = m · 2**e, 0.5 <= |m| < 1
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + jax.lax.stop_gradient(q - x)


def matmul(a, b, how=None):
    return jnp.matmul(_operand(a, how), _operand(b, how), precision=HIGHEST)


def fake_quant(x, bits: int, percentile: float | None = None):
    """Symmetric per-tensor fake quantization, straight-through gradient."""
    if bits <= 0 or bits >= 32:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    mag = jnp.abs(jax.lax.stop_gradient(x)).reshape(-1)
    if percentile is None:
        amax = jnp.max(mag)
    else:
        n = int(mag.shape[0])
        rank = min(n, max(1, math.ceil(percentile / 100.0 * n)))
        amax = jnp.sort(mag)[rank - 1]          # nearest-rank percentile
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    return x + jax.lax.stop_gradient(q - x)


def normalized_edges(n_nodes: int, senders: np.ndarray, receivers: np.ndarray):
    """Edges with one self-loop per node appended, and their weights
    1/sqrt(out_deg(s)) · 1/sqrt(in_deg(r)) (host numpy)."""
    loops = np.arange(n_nodes, dtype=np.int64)
    s = np.concatenate([np.asarray(senders, np.int64), loops])
    r = np.concatenate([np.asarray(receivers, np.int64), loops])
    deg_in = np.bincount(r, minlength=n_nodes).astype(np.float64)
    deg_out = np.bincount(s, minlength=n_nodes).astype(np.float64)
    w = (1.0 / np.sqrt(deg_out[s])) * (1.0 / np.sqrt(deg_in[r]))
    return s.astype(np.int32), r.astype(np.int32), w.astype(np.float32)


def forward(params, x, senders, receivers, weight, model: dict, operands=None):
    """Logits of every node. ``model`` holds the configuration's
    ``layer_dims``, ``weight_bits``, ``act_bits``, ``act_percentile`` and
    ``quant`` (False: no fake quantization)."""
    n = x.shape[0]
    n_layers = len(model["layer_dims"]) - 1

    def aggregate(z):
        return jax.ops.segment_sum(z[senders] * weight[:, None], receivers, n)

    h = x
    for i in range(n_layers):
        w = params[f"w{i}"]
        if model["quant"]:
            w = fake_quant(w, model["weight_bits"])
            h = fake_quant(h, model["act_bits"], model["act_percentile"])
        d_in, d_out = w.shape
        if d_out <= d_in:                        # COIN: transform, then aggregate
            h = aggregate(matmul(h, w, operands))
        else:
            h = matmul(aggregate(h), w, operands)
        h = h + params[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def loss(params, data: dict, model: dict, operands=None):
    logits = forward(params, data["feats"], data["senders"], data["receivers"],
                     data["weight"], model, operands)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, data["labels"][:, None], axis=-1)[:, 0]
    mask = data["label_mask"]
    return ((lse - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def adamw_step(params, m, v, t, grads, opt: dict):
    """One AdamW update. ``t`` (the step, from 1) and the constants are
    float32 whatever the arrays' dtype; each new array is rounded to its
    leaf's dtype. (In bfloat16, b2 = 0.999 rounds to 1, so a bias correction
    computed there is 0 and the update is lost, not rounded.)"""
    b1, b2, eps, lr, wd = opt["b1"], opt["b2"], opt["eps"], opt["lr"], opt["weight_decay"]
    f32 = jnp.float32
    t = jnp.asarray(t, f32)
    c1, c2 = 1 - f32(b1) ** t, 1 - f32(b2) ** t

    def one(p, mm, vv, g):
        g32 = g.astype(f32)
        mm = (b1 * mm.astype(f32) + (1 - b1) * g32).astype(p.dtype)
        vv = (b2 * vv.astype(f32) + (1 - b2) * g32 * g32).astype(p.dtype)
        p32, m32, v32 = p.astype(f32), mm.astype(f32), vv.astype(f32)
        p = (p32 - lr * ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps) + wd * p32)).astype(p.dtype)
        return p, mm, vv

    out = {k: one(params[k], m[k], v[k], grads[k]) for k in params}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def train(params, data: dict, model: dict, opt: dict, steps: int,
          precision: Precision = REFERENCE, step_fault=None):
    """``steps`` AdamW steps from ``params``. Returns the losses, the first
    step's gradients, and the parameters after the last step (host numpy,
    float32).

    ``step_fault`` replaces the loss's data with a broken copy, to read
    what a fault in the program's step would show (`bench.calibrate`)."""
    if step_fault is not None:
        data = step_fault(data)
    dtype = precision.storage
    data = _cast(data, dtype)

    @jax.jit
    def step(p, m, v, t, data):
        value, grads = jax.value_and_grad(loss)(p, data, model, precision.operands)
        p, m, v = adamw_step(p, m, v, t, grads, opt)
        return p, m, v, value, grads

    p = _cast(params, dtype)
    m = v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first = [], None
    for i in range(steps):
        p, m, v, value, grads = step(p, m, v, jnp.asarray(i + 1, jnp.float32), data)
        losses.append(float(value))
        if first is None:
            first = {k: np.asarray(g, np.float32) for k, g in grads.items()}
    return losses, first, {k: np.asarray(x, np.float32) for k, x in p.items()}
