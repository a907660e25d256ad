"""Read the numbers that decide ``correct`` over many seeds, on the chip.

    python -m bench.calibrate --workload train-nell --seeds 12 --control-seeds 3

One process. For each seed the program goes through the cell's own timed
path (the same driver code a run uses) and its readings against the plain
reference are printed; on the first ``--control-seeds`` seeds so are the
readings of each control in `bench.reference.gcn.CONTROLS` (the reference
put in the program's place one precision step down) and of the reference
with half of the batch left out. Besides the compared numbers, each record
holds every step's loss gap (``loss_gaps``) and each leaf's gaps. A step
that returns its state unchanged reads 1 on ``change_gap`` by its
definition and needs no run. One JSON line per seed; the limits in the
configuration files are set from these readings (`PERF.md` lists them).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def calibrate_train(cell, seeds, n_control):
    from bench import compare
    from bench.drivers import fullgraph_train as d
    from bench.reference import gcn as ref

    raw, batch, _ = d.prepare(cell)
    ref_data = d.reference_data(raw)

    def half(data):
        n = data["label_mask"].shape[0]
        return dict(data, label_mask=data["label_mask"].at[n // 2:].set(0.0))

    for i, seed in enumerate(seeds):
        trainer, got, p0 = d.first_steps(cell, batch, seed)
        del trainer
        want = d.reference(cell, ref_data, p0)
        rec = {"seed": seed, "losses": {"reference": want["losses"]}}
        runs = {"program": got}
        if i < n_control:
            for name, precision in ref.CONTROLS.items():
                runs[f"control_{name}"] = d.reference(cell, ref_data, p0, precision)
            runs["half_batch"] = d.reference(cell, ref_data, p0, step_fault=half)
        for name, r in runs.items():
            rec[name] = dict(compare.train_readings(r, want),
                             loss_gaps=[abs(a - b) / abs(b)
                                        for a, b in zip(r["losses"], want["losses"])])
            rec[f"{name}_leaves"] = _leaves(r, want)
            rec["losses"][name] = r["losses"]
        yield rec


def _leaves(got, want):
    from bench import compare

    return {"grads": compare.leaf_gaps(got["grads"], want["grads"]),
            "change": compare.leaf_gaps(got["change"], want["change"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.device import NoAccelerator, open_devices

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    try:
        open_devices(cell.chips)
    except NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, str(harness.ROOT / "src"))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if cell.traffic["kind"] != "fullgraph_train":
        raise SystemExit(f"calibrate: no readings for traffic kind {cell.traffic['kind']!r}")
    for rec in calibrate_train(cell, seeds, args.control_seeds):
        print(json.dumps(rec), flush=True)
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
