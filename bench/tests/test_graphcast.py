"""The GraphCast cell on the CPU at a tiny size: a 15° grid, a refinement-2
multimesh of levels 1–2, latent 32, 2 processor layers, 5 channels. The
whole harness runs: a sound run is correct, broken ones are not, and the
reference one precision step down fails a limit."""
from __future__ import annotations

import copy
import json

import numpy as np
import pytest

from bench import compare, harness
from bench.calibrate_graphcast import FAULTS, calibrate, program_fault
from bench.tests.conftest import ROOT, TAG

CELL = "train-graphcast-small"
SEED = 2**33 + 7


@pytest.fixture
def gc_bench(monkeypatch, tmp_path):
    """BENCHMARK.json with the GraphCast cell on a tiny configuration,
    written into `bench/configs` and removed afterwards."""
    from bench import device

    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax_cache")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = json.loads((ROOT / "bench" / "configs" / "graphcast_small-1deg.json").read_text())
    c["name"] = f"{TAG}-graphcast"
    c["model"].update(resolution=15.0, n_lat=13, n_lon=24, n_grid=312, mesh_splits=2,
                      mesh_min_level=1, n_mesh=162, n_mesh_edges=1200, n_g2m=576, n_m2g=936,
                      d_latent=32, n_layers=2, surface_weights=[1.0, 0.1],
                      surface_vars=["a", "b"], atmos_vars=["c"], pressure_levels=[500, 850, 1000],
                      n_vars=5, n_forcings=1, n_static=1, d_grid_in=14)
    path = ROOT / "bench" / "configs" / f"{c['name']}.json"
    path.write_text(json.dumps(c))
    bench["configs"] = [{"name": c["name"], "source": "test", "file": str(path.relative_to(ROOT)),
                         "reduced": [], "why": "test"}]
    w = copy.deepcopy(next(w for w in bench["workloads"] if w["name"] == CELL))
    w["config"] = c["name"]
    bench["workloads"] = [w]
    try:
        yield bench
    finally:
        path.unlink(missing_ok=True)


def run(bench, trace=False):
    return harness.run(CELL, SEED, 1.0, trace, 0.0, require_accelerator=False, bench=bench)


def test_sound_run_is_correct_and_counts_its_graphs(gc_bench):
    r = run(gc_bench)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"setup_s", "step_s"}
    assert r["attempted"] > 0 and r["failed"] == 0


def test_step_that_returns_its_state_unchanged(gc_bench, monkeypatch):
    from repro.train.loop import Trainer

    build = Trainer._build_step

    def unchanged(self, donate):
        step = build(self, False)

        def f(params, opt_state, residual, batch):
            _, _, residual, loss = step(params, opt_state, residual, batch)
            return params, opt_state, residual, loss
        return f

    monkeypatch.setattr(Trainer, "_build_step", unchanged)
    r = run(gc_bench)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] >= 0.99


@pytest.mark.parametrize("fault", FAULTS)
def test_program_faults_fail(gc_bench, fault):
    """The program with a processor layer skipped, or its edge features
    zeroed, against the sound reference."""
    with program_fault(fault):
        r = run(gc_bench)
    assert not r["correct"], r["checks"]


def test_calibration_reads_program_controls_and_faults(gc_bench):
    cell = harness.resolve(gc_bench, CELL)
    (rec,) = list(calibrate(cell, [SEED], 1))
    assert {"program", "control_bf16", "control_bf16_fp8", "fault_skip_layer",
            "fault_zero_edge_features"} <= set(rec)
    limits = cell.config["limits"]["graphcast_train"]
    assert all(rec["program"][k] <= v for k, v in limits.items())


def test_reference_one_precision_step_down_fails_a_limit(gc_bench):
    from bench.device import prng_key
    from bench.drivers import graphcast_train as d
    from bench.reference import gcn
    from repro.models.graphcast import graphcast_init

    cell = harness.resolve(gc_bench, CELL)
    graph, _, _ = d.prepare(cell)
    exs = d.examples(cell, SEED)
    data = d.reference_data(cell, graph)
    p0 = d._flat(graphcast_init(prng_key(SEED, "weights"), d.program_config(cell.config)))
    want = d.reference(cell, data, exs, p0)
    got = d.reference(cell, data, exs, p0, gcn.CONTROLS[gcn.CONTROL])
    checks = compare.checks(compare.train_readings(got, want),
                            cell.config["limits"]["graphcast_train"])
    assert not all(c.ok for c in checks)


def test_traced_run_reports_its_metrics(gc_bench, monkeypatch):
    """On the CPU there is no peak table entry and no device plane: the
    readers that need them give nothing, the counters are there."""
    from bench import harness as h
    from bench import trace as tr

    seen = {}

    def reduce(log_dir):
        return tr.TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1, ops={}, idle_gaps=[])

    monkeypatch.setattr(tr, "reduce", reduce)
    orig = h.load_reader

    def load(name, root=h.ROOT):
        read = orig(name, root)

        def wrapped(view):
            seen.update(view.counters)
            return read(view)
        return wrapped

    monkeypatch.setattr(h, "load_reader", load)
    r = run(gc_bench, trace=True)
    assert {"device_idle.train", "setup_graph_s"} <= set(r["metrics"])
    assert {"n_grid", "n_mesh", "n_mesh_edges", "n_g2m", "n_m2g", "setup_graph_s", "steps",
            "window_s"} <= set(seen)
    assert seen["n_g2m"] == 576 and np.isfinite(r["metrics"]["setup_graph_s"]["value"])
