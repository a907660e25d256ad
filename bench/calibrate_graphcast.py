"""Read the numbers that decide the GraphCast cell's ``correct``, on the chip.

    python -m bench.calibrate_graphcast --seeds 12 --control-seeds 3

One process, the cell ``train-graphcast-small``. For each seed the program
goes through the cell's own timed path (`bench.drivers.graphcast_train`)
and its readings against the plain reference are printed. On the first
``--control-seeds`` seeds so are the readings of each control in
`bench.reference.gcn.CONTROLS` (the reference one precision step down in
its arrays, and in its matmul operands) and of the program with a fault of
`FAULTS` (one processor layer skipped; every edge feature zeroed). A step
that returns its state unchanged reads 1 on ``change_gap`` by definition.
One JSON line per seed; the configuration's limits are set from them
(`PERF.md`).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

CELL = "train-graphcast-small"
FAULTS = ("skip_layer", "zero_edge_features")


@contextlib.contextmanager
def program_fault(name: str | None):
    """The program's forward with ``name`` broken inside (None: sound)."""
    import jax

    from repro.models import graphcast as gc

    if name is None:
        yield
        return
    forward = gc.graphcast_forward

    def broken(params, batch, cfg):
        if name == "skip_layer":
            params = dict(params, processor=jax.tree_util.tree_map(
                lambda a: a[1:], params["processor"]))
        else:
            batch = dict(batch, **{f"{n}_edges": 0.0 * batch[f"{n}_edges"]
                                   for n in ("mesh", "g2m", "m2g")})
        return forward(params, batch, cfg)

    gc.graphcast_forward = broken
    try:
        yield
    finally:
        gc.graphcast_forward = forward


def _record(name, got, want, rec):
    from bench import compare

    rec[name] = dict(compare.train_readings(got, want),
                     loss_gaps=[abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"])])
    rec[f"{name}_leaves"] = {"grads": compare.leaf_gaps(got["grads"], want["grads"]),
                             "change": compare.leaf_gaps(got["change"], want["change"])}
    rec["losses"][name] = got["losses"]


def calibrate(cell, seeds, n_control):
    from bench.drivers import graphcast_train as d
    from bench.reference import gcn

    graph, arrays, _ = d.prepare(cell)
    data = d.reference_data(cell, graph)
    for i, seed in enumerate(seeds):
        exs = d.examples(cell, seed)
        batches = [dict(arrays, grid_inputs=x, grid_target=y) for x, y in exs]
        trainer, got, p0 = d.first_steps(cell, batches, seed)
        del trainer
        want = d.reference(cell, data, exs, p0)
        rec = {"seed": seed, "losses": {"reference": want["losses"]}}
        _record("program", got, want, rec)
        if i < n_control:
            for name, precision in gcn.CONTROLS.items():
                _record(f"control_{name}", d.reference(cell, data, exs, p0, precision), want, rec)
            for fault in FAULTS:
                with program_fault(fault):
                    trainer, broken, _ = d.first_steps(cell, batches, seed)
                del trainer
                _record(f"fault_{fault}", broken, want, rec)
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.device import NoAccelerator, open_devices

    cell = harness.resolve(harness.load_benchmark(), CELL)
    try:
        open_devices(cell.chips)
    except NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, str(harness.ROOT / "src"))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for rec in calibrate(cell, seeds, args.control_seeds):
        print(json.dumps(rec), flush=True)
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
