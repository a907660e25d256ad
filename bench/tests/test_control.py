"""The control of the comparison that decides ``correct``: the reference put
in the program's place one precision step down (`bench.reference.gcn.CONTROL`)
must fail the configuration's limits. On the chip `bench.calibrate` reads it
at the cells' own sizes; here it runs at a size a test run holds."""
from __future__ import annotations

import numpy as np
import pytest

from bench import compare, data, harness
from bench.device import prng_key
from bench.reference import gcn as ref

CONTROL = ref.CONTROLS[ref.CONTROL]


@pytest.mark.parametrize("cell", ["train-nell", "train-pubmed-bsr"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_training_control_fails_a_limit(tiny_bench, cell, seed):
    from bench.drivers import fullgraph_train as d

    c = harness.resolve(tiny_bench, cell)
    raw = data.make_graph(c.config["dataset"])
    p0 = {k: np.asarray(v) for k, v in
          ref.init_params(prng_key(seed, "weights"), c.config["model"]["layer_dims"]).items()}
    rd = d.reference_data(raw)
    want = d.reference(c, rd, p0)
    readings = compare.train_readings(d.reference(c, rd, p0, CONTROL), want)
    checks = compare.checks(readings, c.config["limits"]["fullgraph_train"])
    assert not all(k.ok for k in checks), readings


@pytest.mark.parametrize("cell", ["train-nell", "train-pubmed-bsr"])
def test_lower_precision_control_takes_its_steps(tiny_bench, cell):
    """The control rounds each update to bfloat16 but takes it: every leaf
    moves about as far as in the reference, not by its rounding alone."""
    from bench.drivers import fullgraph_train as d

    c = harness.resolve(tiny_bench, cell)
    raw = data.make_graph(c.config["dataset"])
    p0 = {k: np.asarray(v) for k, v in
          ref.init_params(prng_key(7, "weights"), c.config["model"]["layer_dims"]).items()}
    rd = d.reference_data(raw)
    want = d.reference(c, rd, p0)["change"]
    got = d.reference(c, rd, p0, CONTROL)["change"]
    for k in want:
        ratio = np.linalg.norm(got[k]) / np.linalg.norm(want[k])
        assert 0.5 < ratio < 1.5, (k, ratio)
