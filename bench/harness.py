"""Resolve a cell by name from `BENCHMARK.json`, run it, build its result.

Everything of one configuration, traffic mix or per-layer metric is a file
of its own, found by the name `BENCHMARK.json` gives it:

* ``bench/configs/<config>.json`` (the entry's ``file``): the model, its
  dataset, its plain reference and the limits of the comparison;
* ``bench/traffic/<traffic>.json``: the mix's parameters; its ``kind``
  names the driver, ``bench/drivers/<kind>.py``;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile

from bench import device as dev
from bench import trace as tr

ROOT = dev.ROOT


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    workload: dict
    config: dict
    traffic: dict


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def resolve(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), w, config, traffic)


def metrics_for(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end or per_layer) this cell reports:
    those that list it, and those without a list whose ``moves`` the cell
    reports (every end-to-end metric without a list)."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


def load_reader(name: str, root: pathlib.Path = ROOT):
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of one traced run."""

    cell: Cell
    counters: dict
    trace: tr.TraceSummary
    peaks: dict


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        root: pathlib.Path = ROOT, require_accelerator: bool = True,
        bench: dict | None = None) -> dict:
    """One run of one cell. Returns the result line's object, its ``checks``
    last. Raises `bench.device.NoAccelerator` before any work where the
    chips are missing."""
    bench = load_benchmark(root) if bench is None else bench
    cell = resolve(bench, name, root)
    devices = dev.open_devices(cell.chips, require_accelerator)
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        out = driver.run(cell, seed, seconds, tr.Tracer(trace, log_dir), t_start, devices)
        summary = tr.reduce(log_dir) if trace else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    facts = out["device"]
    if trace:
        view = RunView(cell, out["counters"], summary, _peaks(facts["kind"], require_accelerator))
        metrics = {}
        for m in metrics_for(bench, name, "per_layer"):
            value = load_reader(m["name"], root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        facts = dict(facts, busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        metrics = {}
        for m in metrics_for(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    result = {"correct": all(c.ok for c in out["checks"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": facts}
    if trace:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                        for c in out["checks"]}
    for m in metrics.values():
        m["value"] = _finite(m["value"])
    return result


def _finite(x: float) -> float | None:
    """A number for the result line; JSON has no infinity or NaN."""
    return x if math.isfinite(x) else None


def _peaks(kind: str, required: bool) -> dict | None:
    from bench import work

    try:
        return work.peaks(kind)
    except KeyError:
        if required:
            raise
        return None
