"""Serving example (deliverable b): batched prefill + KV-cache decode with a
reduced gemma3-style sliding-window LM.

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.transformer_lm import (
    lm_decode_step,
    lm_forward,
    lm_init,
    lm_init_cache,
)


def main() -> None:
    cfg = get_arch("gemma3-12b").make_reduced()
    print(f"model: {cfg.name} ({cfg.n_layers}L, window={cfg.window}, "
          f"global every {cfg.global_every})")
    params = lm_init(jax.random.PRNGKey(0), cfg)

    batch, prompt_len, gen_len, max_len = 4, 24, 16, 64
    prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)

    # ---- prefill: run the prompt once, fill the cache via decode steps
    # (teacher-forced so decode == forward is also checked here).
    cache = lm_init_cache(cfg, batch, max_len)
    decode = jax.jit(lm_decode_step, static_argnames=("cfg",))
    logits = None
    t0 = time.perf_counter()
    for t in range(prompt_len):
        logits, cache = decode(params, cache, prompts[:, t], jnp.asarray(t, jnp.int32), cfg)
    prefill_s = time.perf_counter() - t0
    ref, _ = lm_forward(params, prompts, cfg)
    err = float(jnp.abs(logits - ref[:, -1]).max())
    print(f"prefill {prompt_len} tokens in {prefill_s*1e3:.1f} ms; "
          f"decode-vs-forward max err {err:.2e}")

    # ---- greedy decode
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(prompt_len, prompt_len + gen_len):
        logits, cache = decode(params, cache, tok, jnp.asarray(t, jnp.int32), cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    dt = time.perf_counter() - t0
    gen = jnp.stack(out, 1)
    print(f"generated {gen_len} tokens × {batch} streams in {dt*1e3:.1f} ms "
          f"({batch * gen_len / dt:.0f} tok/s on CPU)")
    print("sample token ids:", gen[0, :10].tolist())


if __name__ == "__main__":
    main()
