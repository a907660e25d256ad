"""A whole run on the CPU at a tiny size, with the timed path broken
underneath: ``correct`` must come out false for every fault a cell can have,
and true with nothing broken."""
from __future__ import annotations

import pytest

from bench import harness

SEED = 2**33 + 5          # wider than 32 bits: every bit of a seed reaches the key


def run(bench, name, seconds=1.0):
    return harness.run(name, SEED, seconds, False, 0.0, require_accelerator=False, bench=bench)


@pytest.mark.parametrize("cell", ["train-nell", "train-pubmed-bsr"])
def test_sound_training_run_is_correct(tiny_bench, cell):
    r = run(tiny_bench, cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "step_s"}
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("cell", ["train-nell", "train-pubmed-bsr"])
def test_step_that_returns_its_state_unchanged(tiny_bench, monkeypatch, cell):
    from repro.train.loop import Trainer

    build = Trainer._build_step

    def unchanged(self, donate):
        step = build(self, False)

        def f(params, opt_state, residual, batch):
            _, _, residual, loss = step(params, opt_state, residual, batch)
            return params, opt_state, residual, loss
        return f

    monkeypatch.setattr(Trainer, "_build_step", unchanged)
    r = run(tiny_bench, cell)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] >= 0.99


@pytest.mark.parametrize("cell", ["train-nell", "train-pubmed-bsr"])
def test_half_of_the_batch_left_out(tiny_bench, monkeypatch, cell):
    from repro.launch import steps

    make = steps.gnn_loss_fn

    def half(*a, **k):
        loss = make(*a, **k)

        def f(params, batch):
            mask = batch["label_mask"]
            return loss(params, dict(batch, label_mask=mask.at[mask.shape[0] // 2:].set(0.0)))
        return f

    monkeypatch.setattr(steps, "gnn_loss_fn", half)
    r = run(tiny_bench, cell)
    assert not r["correct"], r["checks"]
