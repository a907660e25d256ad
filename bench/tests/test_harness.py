"""The harness finds cells, configurations, traffic and metrics by name, and
refuses to run without a chip."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import harness
from bench.tests.conftest import ROOT, TAG, TRAIN


def test_throwaway_files_are_found_by_name(tiny_bench):
    cell = harness.resolve(tiny_bench, TRAIN)
    assert cell.config["name"] == f"{TAG}-coin_gcn-nell"
    assert cell.traffic["check_steps"] == 2
    names = [m["name"] for m in harness.metrics_for(tiny_bench, "train-nell", "per_layer")]
    assert f"{TAG}_metric" in names
    assert f"{TAG}_metric" not in [
        m["name"] for m in harness.metrics_for(tiny_bench, "train-pubmed-bsr", "per_layer")]
    read = harness.load_reader(f"{TAG}_metric")
    assert read(harness.RunView(cell, {"steps": 7}, None, None)) == 7.0


def test_every_metric_of_the_benchmark_has_a_reader_and_every_cell_its_files():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert (ROOT / "bench" / "drivers" / f"{cell.traffic['kind']}.py").is_file()
        e2e = {m["name"] for m in harness.metrics_for(bench, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(bench, w["name"], "per_layer")


def test_every_reader_in_the_metrics_directory_loads():
    names = [p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")]
    assert {m["name"] for m in harness.load_benchmark()["per_layer"]} <= set(names)
    for name in names:
        assert callable(harness.load_reader(name))


def test_a_run_reports_the_cells_end_to_end_metrics(tiny_bench):
    r = harness.run("train-nell", 3, 0.5, False, 0.0, require_accelerator=False, bench=tiny_bench)
    assert set(r["metrics"]) == {"setup_s", "step_s"}
    assert r["metrics"]["step_s"]["unit"] == "s"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert set(r) == {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "train-nell", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cli(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_without_the_program_the_command_exits_nonzero(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _cli(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_keeps_to_the_contract():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[g]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in bench["configs"]:
        assert pathlib.Path(ROOT / c["file"]).is_file()
