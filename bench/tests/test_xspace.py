"""The program's names in a chip trace (`bench.xspace`).

On the trace `test_trace.py` reads, recorded before the program had its
spans and scopes: the event-metadata decoder, and the reduction's numbers
unchanged. On a short trace of `train-pubmed-bsr` recorded on a TPU v5e
with them (``bench/tests/data/train-pubmed-bsr-spans.xplane.pb``): the
calibration's device time, the idle time inside ``train.sync``, and the
kernels still found by name."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace as tr
from bench import xspace
from bench.metrics_common import FUSED_FORWARD, KERNELS

DATA = pathlib.Path(__file__).parent / "data"
OLD = DATA / "train-pubmed-bsr.xplane.pb"
NEW = DATA / "train-pubmed-bsr-spans.xplane.pb"


@pytest.fixture(scope="module")
def old():
    return xspace.read(OLD)


@pytest.fixture(scope="module")
def new():
    return xspace.read(NEW)


def _one(ops: dict, prefix: str) -> str:
    (name,) = [n for n in ops if n.startswith(prefix)]
    return name


def _steps(s: xspace.Spans) -> int:
    return s.span_counts()["train.step"]


def test_decoder_reads_tf_op_and_source_of_the_calibration_sort(old):
    stats = old.ops[_one(old.summary.ops, "%sort.2 ")]
    assert stats["tf_op"] == "jit(step)/jvp()/top_k"
    assert stats["source"].endswith("src/repro/core/quant.py:66")


def test_decoder_keys_ops_as_the_reduction_does(old):
    # Ops the compiler made (copies, the sort's iota) carry no tf_op.
    assert set(old.ops) <= set(old.summary.ops)
    with_stats = sum(op.seconds for name, op in old.summary.ops.items() if name in old.ops)
    assert with_stats > 0.99 * sum(op.seconds for op in old.summary.ops.values())


def test_no_runtime_event_is_taken_for_a_program_span(old):
    from jax.profiler import ProfileData

    names = {ev.name for plane in ProfileData.from_file(str(OLD)).planes
             if plane.name.startswith("/host:") for line in plane.lines for ev in line.events}
    assert "np.asarray(jax.Array)" in names and "bench.train.step" in names
    assert not [n for n in names if xspace.PROGRAM_SPAN.match(n)]
    assert old.spans == [] and old.span_idle == {}


def test_without_program_spans_the_reduction_reads_as_before(old):
    base = tr.reduce(OLD)
    assert old.idle_gaps == base.idle_gaps
    assert old.summary.busy_s == base.busy_s and old.summary.window_s == base.window_s
    assert {n: op.seconds for n, op in old.summary.ops.items()} == {
        n: op.seconds for n, op in base.ops.items()}
    assert old.idle_s == pytest.approx(base.window_s - base.busy_s, abs=1e-9)
    assert old.scope_seconds("quant.calibrate") == 0.0


@pytest.mark.parametrize("tf_op,scope,found", [
    ("jit(step)/jvp(quant.calibrate)/top_k", "quant.calibrate", True),
    ("jit(step)/transpose(jvp(quant.calibrate))/abs", "quant.calibrate", True),
    ("jit(step)/quant.calibrate/abs", "quant.calibrate", True),
    ("jit(step)/jvp()/top_k", "quant.calibrate", False),
    ("jit(step)/jvp(quant.calibrate_x)/top_k", "quant.calibrate", False),
    ("", "quant.calibrate", False),
])
def test_scope_is_a_component_of_the_name_stack(tf_op, scope, found):
    assert xspace.in_scope(tf_op, scope) is found


def test_idle_goes_to_the_innermost_open_span():
    spans = [("train.step", 0, 100), ("train.dispatch", 0, 10), ("train.sync", 10, 90)]
    gaps = [(5, 20), (85, 120), (130, 140)]
    got = xspace.attribute(gaps, spans)
    assert got == pytest.approx({"train.dispatch": 5e-9, "train.sync": 15e-9,
                                 "train.step": 10e-9})


def test_new_trace_is_small_and_names_every_step(new):
    assert NEW.stat().st_size < 1_000_000
    counts = new.span_counts()
    assert counts["train.step"] == counts["train.dispatch"] == counts["train.sync"] >= 3
    by = {name: [(a, b) for n, a, b in new.spans if n == name] for name in counts}
    for (a, b), (da, db), (sa, sb) in zip(by["train.step"], by["train.dispatch"],
                                          by["train.sync"]):
        assert a <= da <= db <= sa <= sb <= b


def test_calibration_scope_reads_the_percentile_sorts(new):
    ms = 1e3 * new.scope_seconds("quant.calibrate") / _steps(new)
    assert 27.0 <= ms <= 28.0
    sorts = sum(op.seconds for name, op in new.summary.ops.items() if " sort(" in name)
    assert new.scope_seconds("quant.calibrate") >= sorts


def test_sync_idle_reads_the_gap_between_steps(new):
    ms = 1e3 * new.span_idle["train.sync"] / _steps(new)
    assert 1.7 <= ms <= 3.0
    assert sum(new.span_idle.values()) <= new.idle_s + 1e-9
    assert new.idle_gaps[0][0] == "train.sync"


def test_kernels_are_still_the_two_forward_fused_layers(new):
    kernels = [n for n in new.summary.ops if "tpu_custom_call" in n]
    assert len(kernels) == 2
    assert new.summary.op_seconds(FUSED_FORWARD) == pytest.approx(
        new.summary.op_seconds(KERNELS))
    assert new.summary.op_seconds(KERNELS) > 0
