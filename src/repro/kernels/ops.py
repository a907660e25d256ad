"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels always compile natively; interpret mode is for the CPU
only, where `interpret=None` picks it.

The graph kernels (`bsr_spmm`, `fused_gcn_layer`) carry custom VJPs so the
training path can differentiate straight through the pallas_call: the
backward of a blocked SpMM is the blocked-TRANSPOSE SpMM, expressed here as
a gathered einsum + scatter-add over the same ragged (vals, cols, lens)
tables (padding tiles masked out), so no transposed block structure needs
to be built or shipped. Integer operands (cols/lens) get symbolic-zero
cotangents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsr_spmm import bsr_spmm_pallas
from repro.kernels.fused_gcn import fused_gcn_layer_pallas
from repro.kernels.fm_interaction import fm_interaction_pallas
from repro.kernels.flash_attention import flash_attention_pallas

__all__ = ["bsr_spmm", "fused_gcn_layer", "fm_interaction", "flash_attention", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto(interpret: bool | None) -> bool:
    if on_tpu():
        if interpret:
            raise ValueError("Pallas interpret mode is for the CPU only; "
                             "on a TPU the kernels compile natively")
        return False
    return True if interpret is None else interpret


def _pick_f_tile(F: int) -> int:
    return 512 if F >= 512 else max(128, 1 << (F - 1).bit_length())


def _pad_rows(z, block: int):
    """Row-pad a dense operand to the block grid (static shapes, zero rows)."""
    pad = (-z.shape[0]) % block
    return jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1)) if pad else z


def _int_zero(x):
    """Symbolic-zero cotangent for integer operands (cols/lens)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _tile_mask(cols, lens):
    """(R, T, 1, 1) validity mask of the ragged tile tables."""
    R, T = cols.shape
    return (jnp.arange(T)[None, :] < lens[:, None]).astype(jnp.float32)[:, :, None, None]


def _bsr_t_apply(vals, cols, mask, g, n_z_rows: int):
    """Blocked-transpose apply: dZ[c] = Σ_{(r,t): cols[r,t]=c} vals[r,t]ᵀ·g[r].

    ``g`` is (R·B, F) row-cotangents; returns (n_z_rows, F). This IS the
    backward of the blocked SpMM, written as einsum + scatter-add over the
    forward's own ragged tables — no transposed block structure needed.
    """
    R, T = cols.shape
    B = vals.shape[-1]
    F = g.shape[-1]
    gb = g.reshape(R, B, F)
    contrib = jnp.einsum(
        "rtij,rif->rtjf", (vals * mask).astype(jnp.float32), gb.astype(jnp.float32)
    )
    dz = jnp.zeros((n_z_rows // B, B, F), jnp.float32)
    dz = dz.at[cols.reshape(-1)].add(contrib.reshape(R * T, B, F))
    return dz.reshape(n_z_rows, F)


def _bsr_dvals(cols, mask, g, z):
    """dvals[r,t] = g[r] · Z[cols[r,t]]ᵀ (zero on padding tiles)."""
    R, T = cols.shape
    F = z.shape[-1]
    B = g.shape[0] // R
    zb = z.reshape(-1, B, F)[cols]                       # (R, T, B, F)
    gb = g.reshape(R, B, F)
    return jnp.einsum(
        "rif,rtjf->rtij", gb.astype(jnp.float32), zb.astype(jnp.float32)
    ) * mask


# --------------------------------------------------------- bsr_spmm (+ VJP)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bsr_diff(f_tile: int, interpret: bool, vals, cols, lens, z):
    return bsr_spmm_pallas(vals, cols, lens, z, f_tile=f_tile, interpret=interpret)


def _bsr_diff_fwd(f_tile, interpret, vals, cols, lens, z):
    out = _bsr_diff(f_tile, interpret, vals, cols, lens, z)
    return out, (vals, cols, lens, z)


def _bsr_diff_bwd(f_tile, interpret, res, g):
    vals, cols, lens, z = res
    mask = _tile_mask(cols, lens)
    dz = _bsr_t_apply(vals, cols, mask, g, z.shape[0]).astype(z.dtype)
    dvals = _bsr_dvals(cols, mask, g, z).astype(vals.dtype)
    return dvals, _int_zero(cols), _int_zero(lens), dz


_bsr_diff.defvjp(_bsr_diff_fwd, _bsr_diff_bwd)


def bsr_spmm(vals, cols, z, lens=None, f_tile: int | None = None,
             interpret: bool | None = None):
    """Ragged block-sparse Ã·Z (DESIGN.md §2).

    Pads the feature dim to the tile size and the rows of ``z`` to the block
    grid if needed; the output has ``R·B`` rows (the RECEIVER block grid —
    fewer than ``z``'s rows for the rectangular halo matrices, where ``z``
    is the wider ``[local ‖ halo]`` table). ``lens`` is the
    per-block-row valid tile count from
    `repro.graph.structure.BlockedAdjacency.row_nnzb`; omitted (None), every
    tile is treated as valid — correct for any layout (padding tiles are
    zero) but pays the dense-T worst case the ragged path exists to avoid.
    """
    R, T, B, _ = vals.shape
    F = z.shape[1]
    if f_tile is None:
        f_tile = _pick_f_tile(F)
    if lens is None:
        lens = jnp.full((R,), T, jnp.int32)
    pad = (-F) % f_tile
    if pad:
        z = jnp.pad(z, ((0, 0), (0, pad)))
    z = _pad_rows(z, B)
    out = _bsr_diff(f_tile, _auto(interpret), vals, cols, lens, z)
    return out[:, :F] if pad else out


# -------------------------------------------------- fused_gcn_layer (+ VJP)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_diff(order: str, relu: bool, f_tile: int, interpret: bool,
                vals, cols, lens, x, w, b):
    return fused_gcn_layer_pallas(
        vals, cols, lens, x, w, b, order=order, relu=relu, f_tile=f_tile,
        interpret=interpret,
    )


def _fused_diff_fwd(order, relu, f_tile, interpret, vals, cols, lens, x, w, b):
    out = _fused_diff(order, relu, f_tile, interpret, vals, cols, lens, x, w, b)
    return out, (vals, cols, lens, x, w, b, out)


def _fused_diff_bwd(order, relu, f_tile, interpret, res, g):
    """Layer backward: dpre = g·act'(pre), then the two matmul transposes —
    the aggregation transpose is the blocked scatter-add of `_bsr_t_apply`,
    the A·X recompute (aggregation-first) re-runs the non-fused kernel."""
    vals, cols, lens, x, w, b, out = res
    mask = _tile_mask(cols, lens)
    g = g.astype(jnp.float32)
    if relu:
        # act' from the saved output: relu(pre) > 0 ⇔ pre > 0 (a.e.).
        g = g * (out > 0)
    db = g.sum(axis=0, keepdims=True).astype(b.dtype)
    wf = w.astype(jnp.float32)
    if order == "feature_first":
        # pre = Ã·(x@w) + b
        z = (x.astype(jnp.float32) @ wf).astype(x.dtype)       # recompute Z
        dz = _bsr_t_apply(vals, cols, mask, g, x.shape[0])     # Ãᵀ·dpre
        dvals = _bsr_dvals(cols, mask, g, z)
        dw = x.astype(jnp.float32).T @ dz
        dx = dz @ wf.T
    else:
        # pre = (Ã·x)·w + b — recompute M = Ã·x through the SpMM kernel.
        m = bsr_spmm_pallas(vals, cols, lens, x, f_tile=x.shape[1], interpret=interpret)
        dw = m.astype(jnp.float32).T @ g
        dm = g @ wf.T                                          # (R·B, F_in)
        dvals = _bsr_dvals(cols, mask, dm, x)
        dx = _bsr_t_apply(vals, cols, mask, dm, x.shape[0])
    return (
        dvals.astype(vals.dtype), _int_zero(cols), _int_zero(lens),
        dx.astype(x.dtype), dw.astype(w.dtype), db,
    )


_fused_diff.defvjp(_fused_diff_fwd, _fused_diff_bwd)


def fused_gcn_layer(vals, cols, lens, x, w, b, order: str = "feature_first",
                    relu: bool = True, f_tile: int | None = None,
                    interpret: bool | None = None):
    """One fused GCN layer act(Ã·(X·W) + b) / act((Ã·X)·W + b) — see
    `repro.kernels.fused_gcn`.

    Handles the alignment the kernel requires: rows of ``x`` pad to the
    block grid, F_in/F_out pad to 128 lanes (zero weight rows/cols, sliced
    back off). Returns (R·B, F_out) — callers slice to their real node
    count. Accumulation is fp32; pass bf16 ``vals``/``x``/``w`` for the
    half-width MXU path.
    """
    R, T, B, _ = vals.shape
    F_in, F_out = w.shape
    if lens is None:
        lens = jnp.full((R,), T, jnp.int32)
    if order == "aggregation_first":
        # The whole weight + the (B, F_in) accumulator stay VMEM-resident;
        # fail early with a real error instead of an opaque Mosaic OOM.
        resident = 4 * (F_in * F_out + 2 * B * F_in + B * F_out + B * B)
        if resident > 14_000_000:
            raise ValueError(
                f"aggregation_first fused layer needs ~{resident / 1e6:.0f} MB "
                f"VMEM-resident (F_in={F_in}, F_out={F_out}) — past the ~16 MB "
                "budget; use order='feature_first' or the unfused bsr_spmm path"
            )
    pad_in = (-F_in) % 128
    f_tile = _pick_f_tile(F_out) if f_tile is None else f_tile
    pad_out = (-F_out) % (f_tile if order == "feature_first" else 128)
    x = _pad_rows(x, B)
    if pad_in:
        x = jnp.pad(x, ((0, 0), (0, pad_in)))
        w = jnp.pad(w, ((0, pad_in), (0, 0)))
    if pad_out:
        w = jnp.pad(w, ((0, 0), (0, pad_out)))
    b2 = jnp.reshape(b, (1, F_out))
    if pad_out:
        b2 = jnp.pad(b2, ((0, 0), (0, pad_out)))
    out = _fused_diff(
        order, relu, min(f_tile, F_out + pad_out), _auto(interpret),
        vals, cols, lens, x, w, b2,
    )
    return out[:, :F_out] if pad_out else out


def fm_interaction(emb, b_tile: int = 256, interpret: bool | None = None):
    B = emb.shape[0]
    while B % b_tile:
        b_tile //= 2
    return fm_interaction_pallas(emb, b_tile=max(b_tile, 1), interpret=_auto(interpret))


def flash_attention(q, k, v, window=None, causal: bool = True,
                    bq: int = 256, bk: int = 256, interpret: bool | None = None):
    return flash_attention_pallas(
        q, k, v, window=window, bq=bq, bk=bk, causal=causal, interpret=_auto(interpret)
    )
