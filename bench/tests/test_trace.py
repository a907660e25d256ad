"""The trace reduction, on a short trace of `train-pubmed-bsr` recorded on a
TPU v5e (``bench/tests/data/train-pubmed-bsr.xplane.pb``: a window of a few
training steps of the fused ragged-BSR kernels)."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace as tr
from bench.metrics_common import FUSED_FORWARD, KERNELS

TRACE = pathlib.Path(__file__).parent / "data" / "train-pubmed-bsr.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tr.reduce(TRACE)


def test_window_and_busy_time(summary):
    assert summary.n_devices == 1
    assert 0.1 < summary.window_s < 1.0
    assert 0.0 < summary.busy_s <= summary.window_s
    # Busy is a union: never more than the ops' summed time.
    assert summary.busy_s <= sum(op.seconds for op in summary.ops.values()) + 1e-9


def test_pallas_kernels_are_found_by_their_custom_call(summary):
    kernels = [op for name, op in summary.ops.items() if 'tpu_custom_call' in name]
    assert len(kernels) == 2                       # the two fused GCN layers
    assert all("fused_gcn_layer_pallas" in op.name for op in kernels)
    assert summary.op_seconds(KERNELS) == pytest.approx(sum(op.seconds for op in kernels))
    assert 0.0 < summary.op_seconds(KERNELS) < summary.busy_s


def test_roofline_reads_the_forward_fused_kernels_alone(summary):
    import re

    assert summary.op_seconds(FUSED_FORWARD) == pytest.approx(summary.op_seconds(KERNELS))
    rx = re.compile(FUSED_FORWARD)
    target = 'custom_call_target="tpu_custom_call"'
    assert rx.search(f"%jit_fused_gcn_layer_pallas__ = f32[8,128] custom-call(), {target}")
    for other in ("%transpose_jvp_jit_fused_gcn_layer_pallas__.2",   # a backward
                  "%jvp_jit_bsr_spmm_pallas__.1", "%jit_fused_gcn_layer_bwd_pallas__.1"):
        assert not rx.search(f"{other} = f32[8,128] custom-call(), {target}")
    assert not rx.search("%jit_fused_gcn_layer_pallas__ = f32[8,128] fusion()")


def test_breakdown_names_ops_and_idle_gaps(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    seconds = [s for _, s in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert b["device_ops"][0][0].startswith("%sort")     # the QAT percentile sort
    assert all(name.startswith(("bench.", "host.")) for name, _ in b["idle_gaps"])
    assert "bench.train.step" in {name for name, _ in b["idle_gaps"]}


def test_short_name_drops_layouts_and_operands():
    text = ('%jvp_jit_fused_gcn_layer_pallas__.4 = f32[19840,128]{1,0:T(8,128)S(1)} '
            'custom-call(s32[155,60]{1,0:T(8,128)S(1)} %copy.59), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[155,60]{1,0}}')
    assert tr.short_name(text) == ("%jvp_jit_fused_gcn_layer_pallas__.4 = f32[19840,128] "
                                   "custom-call tpu_custom_call")
    assert tr.short_name("%sort.2 = (f32[9858500]{0:T(1024)}, s32[9858500]{0:T(1024)}) "
                         "sort(f32[9858500]{0} %r)") == "%sort.2 = (f32[9858500], s32[9858500]) sort"


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
