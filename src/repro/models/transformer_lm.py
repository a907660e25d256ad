"""Decoder-only transformer LM covering the 5 assigned LM architectures.

One config-driven implementation provides:
  * dense SwiGLU or MoE FFN (moonshot 64e/top-6, olmoe 64e/top-8),
  * GQA / MQA (granite kv=1),
  * mixed sliding-window / global layers (gemma3 5:1) expressed as a traced
    per-layer window vector so the whole stack lowers as ONE lax.scan,
  * train forward (chunked flash-style attention), prefill, and KV-cache
    decode paths,
  * optional grouped sliding cache (local layers keep only `window` KV
    entries) — the beyond-paper memory optimization for the 500k cell.

Params are dicts with layer-stacked leaves (leading axis = n_layers) so the
layer stack lowers as one scan and its HLO stays small.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.policy import NO_POLICY, ShardingPolicy
from repro.nn.attention import (
    AttentionConfig,
    attention_apply,
    attention_decode,
    attention_init,
)


def _attn(layer_p, h, cfg: "LMConfig", win, policy: ShardingPolicy):
    h = policy.constrain(h, "act")
    out = attention_apply(layer_p["attn"], h, cfg.attn, window=win)
    return policy.constrain(out, "act")
from repro.nn.layers import rms_norm, silu
from repro.nn.moe import MoEConfig, moe_apply, moe_init

__all__ = ["LMConfig", "lm_init", "lm_forward", "lm_loss", "lm_prefill", "lm_decode_step", "lm_init_cache"]

GLOBAL_WINDOW = np.int32(2**30)  # "window" meaning full causal attention


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    moe_experts: int | None = None
    moe_top_k: int | None = None
    moe_groups: int = 1          # hierarchical dispatch groups (= data shards)
    moe_capacity_factor: float = 1.25
    window: int | None = None          # sliding window for local layers
    global_every: int | None = None    # gemma3: every 6th layer global
    rope_theta: float = 10_000.0
    kv_chunk: int = 1024
    tie_embeddings: bool = True
    # Unroll the layer scan in the lowered HLO. XLA's cost_analysis counts
    # a while-loop body ONCE, so a rolled scan under-reports
    # FLOPs/bytes/collectives by ~n_layers.
    unroll_layers: bool = False
    # Rematerialize layer activations in backward (jax.checkpoint on the
    # scan body): trades recompute FLOPs for peak-memory (§Perf lever).
    remat: bool = False

    @property
    def scan_unroll(self) -> int | bool:
        return self.n_layers if self.unroll_layers else 1

    @property
    def attn(self) -> AttentionConfig:
        return AttentionConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            d_head=self.d_head,
            rope_theta=self.rope_theta,
            kv_chunk=self.kv_chunk,
        )

    @property
    def is_moe(self) -> bool:
        return self.moe_experts is not None

    @property
    def sub_quadratic(self) -> bool:
        """True iff most layers are sliding-window (long_500k eligibility)."""
        return self.window is not None

    def moe_cfg(self) -> MoEConfig:
        assert self.is_moe
        return MoEConfig(
            num_experts=self.moe_experts,
            top_k=self.moe_top_k,
            d_model=self.d_model,
            d_ff=self.d_ff,
            groups=self.moe_groups,
            capacity_factor=self.moe_capacity_factor,
        )

    def window_sizes(self) -> np.ndarray:
        """Per-layer attention window (int32). Global layers get 2^30."""
        if self.window is None:
            return np.full(self.n_layers, GLOBAL_WINDOW, np.int32)
        ws = np.full(self.n_layers, self.window, np.int32)
        if self.global_every:
            ws[self.global_every - 1 :: self.global_every] = GLOBAL_WINDOW
        return ws

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd = self.d_head or d // self.n_heads
        attn = d * hd * (self.n_heads * 2) + d * hd * (self.n_kv_heads * 2)
        if self.is_moe:
            ffn = d * self.moe_experts + self.moe_experts * 3 * d * f
        else:
            ffn = 3 * d * f
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + v * d + d

    def active_param_count(self) -> int:
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        hd = self.d_head or d // self.n_heads
        attn = d * hd * (self.n_heads * 2) + d * hd * (self.n_kv_heads * 2)
        ffn = d * self.moe_experts + self.moe_top_k * 3 * d * f
        return self.n_layers * (attn + ffn + 2 * d) + self.vocab * d + d


# --------------------------------------------------------------------- params
def _layer_init(key: jax.Array, cfg: LMConfig, dtype) -> dict:
    ka, km = jax.random.split(key)
    p = {
        "attn": attention_init(ka, cfg.attn, dtype),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.is_moe:
        p["moe"] = moe_init(km, cfg.moe_cfg(), dtype)
    else:
        k1, k2, k3 = jax.random.split(km, 3)
        d, f = cfg.d_model, cfg.d_ff
        std_in, std_out = (1.0 / d) ** 0.5, (1.0 / f) ** 0.5
        p["mlp"] = {
            "w_gate": jax.random.normal(k1, (d, f), dtype) * std_in,
            "w_up": jax.random.normal(k2, (d, f), dtype) * std_in,
            "w_down": jax.random.normal(k3, (f, d), dtype) * std_out,
        }
    return p


def lm_init(key: jax.Array, cfg: LMConfig, dtype=jnp.float32) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = [_layer_init(k, cfg, dtype) for k in layer_keys]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    params = {
        "embed": jax.random.normal(ke, (cfg.vocab, cfg.d_model), dtype) * 0.02,
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(kh, (cfg.d_model, cfg.vocab), dtype) * 0.02
    return params


def lm_param_shapes(cfg: LMConfig, dtype=jnp.float32):
    """ShapeDtypeStruct pytree — lowering without allocation."""
    return jax.eval_shape(lambda k: lm_init(k, cfg, dtype), jax.random.PRNGKey(0))


# -------------------------------------------------------------------- forward
def _ffn(layer_p: dict, x2: jnp.ndarray, cfg: LMConfig, policy: ShardingPolicy):
    B, S, D = x2.shape
    if cfg.is_moe:
        flat = x2.reshape(B * S, D)
        out, aux = moe_apply(layer_p["moe"], flat, cfg.moe_cfg(), policy=policy)
        return out.reshape(B, S, D), aux
    m = layer_p["mlp"]
    h = silu(x2 @ m["w_gate"]) * (x2 @ m["w_up"])
    h = policy.constrain(h, "ffn_hidden")
    return h @ m["w_down"], jnp.zeros((), jnp.float32)


def lm_forward(
    params: dict,
    tokens: jnp.ndarray,               # (B, S) int32
    cfg: LMConfig,
    policy: ShardingPolicy = NO_POLICY,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V), aux_loss)."""
    x = params["embed"][tokens] * (cfg.d_model ** 0.5)
    x = policy.constrain(x, "act")
    windows = jnp.asarray(cfg.window_sizes())

    def layer(carry, xs):
        x, aux = carry
        layer_p, win = xs
        h = rms_norm(x, layer_p["ln1"])
        h = _attn(layer_p, h, cfg, win, policy)
        x = x + h
        h2 = rms_norm(x, layer_p["ln2"])
        f, a = _ffn(layer_p, h2, cfg, policy)
        x = policy.constrain(x + f, "act")
        return (x, aux + a), None

    if cfg.remat:
        layer = jax.checkpoint(layer)
    (x, aux), _ = jax.lax.scan(
        layer, (x, jnp.zeros((), jnp.float32)), (params["layers"], windows),
        unroll=cfg.scan_unroll,
    )
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    logits = policy.constrain(logits, "logits")
    return logits, aux


def lm_loss(
    params: dict,
    tokens: jnp.ndarray,
    cfg: LMConfig,
    policy: ShardingPolicy = NO_POLICY,
    aux_weight: float = 0.01,
) -> jnp.ndarray:
    """Next-token cross entropy (vocab-sharded-safe logsumexp form)."""
    logits, aux = lm_forward(params, tokens[:, :-1], cfg, policy)
    labels = tokens[:, 1:]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + aux_weight * aux


# -------------------------------------------------------------------- serving
def lm_prefill(
    params: dict,
    tokens: jnp.ndarray,
    cfg: LMConfig,
    policy: ShardingPolicy = NO_POLICY,
) -> jnp.ndarray:
    """Prefill: logits for the LAST position only (the serving quantity)."""
    logits, _ = lm_forward(params, tokens, cfg, policy)
    return logits[:, -1]


def lm_init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=jnp.float32) -> dict:
    hd = cfg.attn.head_dim
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def lm_cache_shapes(cfg: LMConfig, batch: int, max_len: int, dtype=jnp.float32):
    return jax.eval_shape(lambda: lm_init_cache(cfg, batch, max_len, dtype))


def lm_decode_step(
    params: dict,
    cache: dict,                        # {"k","v"}: (L, B, Smax, Hk, Dh)
    token: jnp.ndarray,                 # (B,) int32 current token ids
    pos: jnp.ndarray,                   # scalar int32
    cfg: LMConfig,
    policy: ShardingPolicy = NO_POLICY,
) -> tuple[jnp.ndarray, dict]:
    """One decode step for all layers; returns (next-token logits, new cache)."""
    B = token.shape[0]
    x = params["embed"][token][:, None, :] * (cfg.d_model ** 0.5)
    x = policy.constrain(x, "dec_act")
    windows = jnp.asarray(cfg.window_sizes())

    def layer(x, xs):
        layer_p, win, ck, cv = xs
        h = rms_norm(x, layer_p["ln1"])
        h, new_c = attention_decode(
            layer_p["attn"], h, {"k": ck, "v": cv}, pos, cfg.attn, window=win
        )
        x = x + h
        h2 = rms_norm(x, layer_p["ln2"])
        f, _ = _ffn(layer_p, h2, cfg, policy)
        x = policy.constrain(x + f, "dec_act")
        return x, (new_c["k"], new_c["v"])

    x, (nk, nv) = jax.lax.scan(
        layer, x, (params["layers"], windows, cache["k"], cache["v"]),
        unroll=cfg.scan_unroll,
    )
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return policy.constrain(logits, "dec_logits"), {"k": nk, "v": nv}
