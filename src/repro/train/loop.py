"""Training loop with fault tolerance and straggler monitoring.

Features (DESIGN.md §3):
  * jitted train step (loss + grads + optimizer update), optional gradient
    accumulation (lax.scan over microbatches),
  * optional gradient compression with error feedback (train/compression.py),
  * step-level checkpointing (atomic; train/checkpoint.py) and restart —
    `Trainer.fit` resumes from the latest complete checkpoint after a crash,
  * straggler monitoring: per-step wall time vs an EMA; steps slower than
    `straggler_factor ×` EMA are logged as events (at pod scale the same
    signal drives re-sharding / hot-spare swap; see train/elastic.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.train.compression import error_feedback_update, int8_compress, int8_decompress
from repro.train.optimizer import Optimizer

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    log_every: int = 10
    grad_accum: int = 1
    compress_grads: bool = False
    straggler_factor: float = 3.0
    ema_decay: float = 0.9


class Trainer:
    def __init__(
        self,
        loss_fn: Callable,              # (params, batch) -> scalar loss
        optimizer: Optimizer,
        params: Any,
        cfg: TrainerConfig = TrainerConfig(),
        donate: bool = True,
    ):
        self.cfg = cfg
        self.opt = optimizer
        self.params = params
        self.opt_state = optimizer.init(params)
        self.residual = (
            jax.tree_util.tree_map(jnp.zeros_like, params) if cfg.compress_grads else None
        )
        self.step = 0
        self.straggler_events: list[dict] = []
        self.step_seconds: list[float] = []     # host clock, one per step
        self._ema_dt: float | None = None
        self._loss_fn = loss_fn
        self._step_fn = self._build_step(donate)

    # ------------------------------------------------------------- step build
    def _build_step(self, donate: bool):
        cfg = self.cfg

        def grads_of(params, batch):
            if cfg.grad_accum == 1:
                return jax.value_and_grad(self._loss_fn)(params, batch)
            # batch leaves have a leading microbatch axis of size grad_accum.
            def micro(carry, mb):
                loss, acc = carry
                l, g = jax.value_and_grad(self._loss_fn)(params, mb)
                return (loss + l, jax.tree_util.tree_map(jnp.add, acc, g)), None

            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zero), batch)
            scale = 1.0 / cfg.grad_accum
            return loss * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)

        def step(params, opt_state, residual, batch):
            loss, grads = grads_of(params, batch)
            if cfg.compress_grads:
                def chan(g):
                    q, s = int8_compress(g)
                    return int8_decompress(q, s, g.dtype)

                grads, residual = error_feedback_update(grads, residual, chan)
            new_params, new_opt = self.opt.update(grads, opt_state, params)
            return new_params, new_opt, residual, loss

        dn = (0, 1, 2) if donate else ()
        return jax.jit(step, donate_argnums=dn)

    # ---------------------------------------------------------------- resume
    def resume(self) -> bool:
        """Restore the latest checkpoint if one exists. Returns True if so."""
        if not self.cfg.ckpt_dir:
            return False
        last = latest_step(self.cfg.ckpt_dir)
        if last is None:
            return False
        state = {"params": self.params, "opt": self.opt_state}
        self.step, restored, _meta = restore_checkpoint(self.cfg.ckpt_dir, state, step=last)
        self.params, self.opt_state = restored["params"], restored["opt"]
        return True

    def checkpoint(self) -> None:
        if self.cfg.ckpt_dir:
            save_checkpoint(
                self.cfg.ckpt_dir,
                self.step,
                {"params": self.params, "opt": self.opt_state},
                metadata={"time": time.time()},
            )

    # ------------------------------------------------------------------- fit
    def fit(
        self,
        batches: Iterator[Any],
        max_steps: int,
        crash_at: int | None = None,     # fault-injection hook for tests
        log: Callable[[str], None] = print,
    ) -> list[float]:
        losses = []
        for batch in batches:
            if self.step >= max_steps:
                break
            t0 = time.perf_counter()
            with _obs_trace.span("train.step", args={"step": self.step}):
                with _obs_trace.span("train.dispatch"):
                    self.params, self.opt_state, self.residual, loss = self._step_fn(
                        self.params, self.opt_state, self.residual, batch
                    )
                with _obs_trace.span("train.sync"):
                    loss = float(loss)  # blocks: the span covers device work
            dt = time.perf_counter() - t0
            self.step += 1
            losses.append(loss)
            self.step_seconds.append(dt)
            if _obs_metrics.enabled():
                _obs_metrics.inc("train.steps")
                _obs_metrics.observe("train.step_ms", dt * 1e3)
                _obs_metrics.set_gauge("train.loss", loss)
            # ---- straggler monitor
            if self._ema_dt is not None and dt > self.cfg.straggler_factor * self._ema_dt:
                self.straggler_events.append({"step": self.step, "dt": dt, "ema": self._ema_dt})
            self._ema_dt = dt if self._ema_dt is None else (
                self.cfg.ema_decay * self._ema_dt + (1 - self.cfg.ema_decay) * dt
            )
            if self.step % self.cfg.log_every == 0:
                log(f"step {self.step}: loss={loss:.4f} dt={dt*1e3:.1f}ms")
            if self.cfg.ckpt_dir and self.step % self.cfg.ckpt_every == 0:
                self.checkpoint()
            if crash_at is not None and self.step == crash_at:
                raise RuntimeError(f"injected crash at step {self.step}")
        return losses
