"""CPU tests of the benchmark: tiny throwaway cells, placed in the
benchmark's own directories and found by name, run the whole harness
without a chip."""
from __future__ import annotations

import copy
import json
import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TAG = f"_test{os.getpid()}"
TRAIN = f"{TAG}-train"


def _tiny(config: str, **dataset) -> dict:
    c = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    c["name"] = f"{TAG}-{config}"
    c["dataset"].update(dataset)
    c["model"]["layer_dims"] = [dataset["n_features"], 16, dataset["n_labels"]]
    return c


@pytest.fixture
def tiny_bench(monkeypatch, tmp_path):
    """BENCHMARK.json's cells and metrics over tiny throwaway configurations,
    a throwaway traffic mix and cell, and a throwaway per-layer metric,
    written into `bench/configs`, `bench/traffic` and `bench/metrics` and
    removed afterwards."""
    from bench import device

    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax_cache")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    made = []

    def put(path: pathlib.Path, text: str):
        path.write_text(text)
        made.append(path)

    seg = _tiny("coin_gcn-nell", n_nodes=500, n_edges=2500, n_features=96, n_labels=12)
    bsr = _tiny("coin_gcn-pubmed-bsr", n_nodes=400, n_edges=2000, n_features=64, n_labels=5)
    configs = []
    for c in (seg, bsr):
        f = ROOT / "bench" / "configs" / f"{c['name']}.json"
        put(f, json.dumps(c))
        configs.append({"name": c["name"], "source": "test", "file": str(f.relative_to(ROOT)),
                        "reduced": [], "why": "test"})
    traffic = json.loads((ROOT / "bench" / "traffic" / "fullgraph_train.json").read_text())
    traffic["check_steps"] = 2
    put(ROOT / "bench" / "traffic" / f"{TAG}_train.json", json.dumps(traffic))
    put(ROOT / "bench" / "metrics" / f"{TAG}_metric.py",
        "def read(run):\n    return float(run.counters['steps'])\n")
    bench["configs"] = configs
    workloads = []
    for w in bench["workloads"]:
        w = copy.deepcopy(w)
        w["config"] = bsr["name"] if "bsr" in w["config"] else seg["name"]
        workloads.append(w)
    # A cell of its own on a throwaway traffic mix.
    workloads.append({"name": TRAIN, "config": seg["name"], "traffic": f"{TAG}_train",
                      "chips": 1, "why": "test"})
    bench["workloads"] = workloads
    step_s = next(m for m in bench["end_to_end"] if m["name"] == "step_s")
    step_s["workloads"] = step_s["workloads"] + [TRAIN]
    bench["per_layer"].append({"name": f"{TAG}_metric", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "test", "moves": "step_s",
                               "workloads": ["train-nell"]})
    try:
        yield bench
    finally:
        for p in made:
            p.unlink(missing_ok=True)
