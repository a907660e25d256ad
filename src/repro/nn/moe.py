"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Top-k routing → stable sort by expert id → position-in-expert via exclusive
cumsum of expert counts → scatter into an (E, C, D) buffer → batched expert
GEMMs → gather + gate-weighted combine. All shapes static (capacity factor),
no (T, E, C) one-hot tensors, so it scales to the 64-expert assigned configs
and shards cleanly: the (E, C, D) buffer carries the expert-parallel axis and
pjit lowers dispatch/return as all-to-alls over the `model` mesh axis.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["MoEConfig", "moe_init", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    # Hierarchical dispatch: sort/bucket tokens WITHIN each of `groups`
    # token groups (one per data shard). Keeps the dispatch sort local to a
    # device and turns the expert exchange into the canonical EP all-to-all
    # of a (groups, E, C, D) buffer. groups=1 reproduces the flat (baseline)
    # dispatch.
    groups: int = 1

    def capacity(self, n_tokens: int) -> int:
        cap = int(self.capacity_factor * n_tokens * self.top_k / self.num_experts)
        cap = max(8, -(-cap // 8) * 8)  # round up to 8 for tiling
        # Streams of ≤ 512 tokens (decode steps, small teacher-forced
        # prefills, unit graphs) dispatch drop-free: a token takes at most
        # one slot per expert, so C ≥ T can never overflow. Within that
        # bound stepwise decode equals the full forward pass exactly; above
        # it capacity reverts to the Switch throughput/memory tradeoff and
        # may drop tokens under routing imbalance.
        if n_tokens <= 512:
            cap = max(cap, -(-n_tokens // 8) * 8)
        return cap


def moe_init(key: jax.Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    std_in, std_out = (1.0 / D) ** 0.5, (1.0 / F) ** 0.5
    return {
        "router": jax.random.normal(kr, (D, E), dtype) * std_in,
        "w_gate": jax.random.normal(kg, (E, D, F), dtype) * std_in,
        "w_up": jax.random.normal(ku, (E, D, F), dtype) * std_in,
        "w_down": jax.random.normal(kd, (E, F, D), dtype) * std_out,
    }


def _dispatch(x, gate_vals, expert_idx, E, K, C):
    """Sort-based dispatch of one token group → ((E, C, D) buffer, meta)."""
    T, D = x.shape
    flat_e = expert_idx.reshape(-1)                       # (T·K,)
    flat_t = jnp.tile(jnp.arange(T)[:, None], (1, K)).reshape(-1)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros(E, jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se]
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[se, pos_c].add(jnp.where(keep[:, None], x[st], 0.0))
    return buf, (se, st, sg, keep, pos_c)


def _combine(y, meta, T, D):
    se, st, sg, keep, pos_c = meta
    tok_y = y[se, pos_c] * jnp.where(keep, sg, 0.0)[:, None].astype(y.dtype)
    return jnp.zeros((T, D), y.dtype).at[st].add(tok_y)


def moe_apply(
    p: dict, x: jnp.ndarray, cfg: MoEConfig, policy=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (T, D) flattened tokens → (out: (T, D), aux_loss: scalar).

    aux_loss is the Switch/GShard load-balance loss E·Σ_e f_e·p_e.
    With cfg.groups = G > 1, routing/sort/scatter run independently per
    group of T/G tokens (vmap) and only the (G, E, C_loc, D) buffer crosses
    the expert-parallel axis (all-to-all under pjit).
    """
    T, D = x.shape
    E, K, G = cfg.num_experts, cfg.top_k, cfg.groups
    assert T % G == 0, (T, G)
    logits = x @ p["router"]                              # (T, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)       # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (global statistics)
    frac_tokens = jnp.zeros(E).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    C = cfg.capacity(T // G)
    xg = x.reshape(G, T // G, D)
    gg = gate_vals.reshape(G, T // G, K)
    eg = expert_idx.reshape(G, T // G, K)
    buf, meta = jax.vmap(lambda xi, gi, ei: _dispatch(xi, gi, ei, E, K, C))(xg, gg, eg)
    if policy is not None:
        buf = policy.constrain(buf, "moe_buf")            # (G, E, C, D): EP axis

    # ---- expert GEMMs (SwiGLU), E-major so EP shards over experts
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", buf, p["w_up"]
    )
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])      # (G, E, C, D)
    if policy is not None:
        y = policy.constrain(y, "moe_buf")

    out = jax.vmap(lambda yi, mi: _combine(yi, mi, T // G, D))(y, meta)
    return out.reshape(T, D), aux
