"""Spans and scopes on the profiler's clock (`repro.obs.trace`, `core/quant.py`).

* every ``span()`` / ``traced()`` block is a ``jax.profiler`` annotation of
  the same name, recorder off or on, so a profiler session sees the host
  phases beside the device ops;
* `Trainer.fit` marks each step's ``train.step`` ⊃ ``train.dispatch``,
  ``train.sync``;
* the QAT calibration's ops carry the ``quant.calibrate`` scope in their
  HLO ``op_name`` (the profiler's ``tf_op``) in the compiled train step.
"""
import itertools
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import trace


@pytest.fixture(autouse=True)
def _no_recorder():
    old = trace.set_default_tracer(None)
    yield
    trace.set_default_tracer(old)


def _profiled(fn, log_dir: pathlib.Path) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of the host events a profiler session
    records around ``fn()``, in start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(log_dir.rglob("*.xplane.pb"))))
    events = [(ev.name, int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
              for plane in pd.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events]
    return sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("recorder", [False, True], ids=["recorder_off", "recorder_on"])
def test_span_is_a_profiler_annotation(tmp_path, recorder):
    if recorder:
        trace.enable_tracing()

    def body():
        with trace.span("layer.outer"):
            with trace.span("layer.inner"):
                pass

    events = _profiled(body, tmp_path)
    (outer,), (inner,) = _named(events, "layer.outer"), _named(events, "layer.inner")
    assert _inside(inner, outer)
    chrome = trace.default_tracer()
    if recorder:
        assert {e["name"] for e in chrome.events() if e["ph"] == "X"} == {"layer.outer",
                                                                        "layer.inner"}
    else:
        assert chrome is None


@pytest.mark.parametrize("recorder", [False, True], ids=["recorder_off", "recorder_on"])
def test_traced_is_a_profiler_annotation(tmp_path, recorder):
    if recorder:
        trace.enable_tracing()

    @trace.traced("layer.fn")
    def fn(x):
        return x + 1

    events = _profiled(lambda: fn(1), tmp_path)
    assert len(_named(events, "layer.fn")) == 1


def test_spans_without_a_profiler_record_nothing_and_nest():
    """No session: the annotations are inert, and the null span's stack of
    open annotations is empty again after nested blocks."""
    with trace.span("layer.a"):
        with trace.span("layer.b"):
            pass
    assert trace._NULL_SPAN._open.stack == []


def test_trainer_fit_marks_dispatch_and_sync_inside_each_step(tmp_path):
    from repro.train.loop import Trainer, TrainerConfig
    from repro.train.optimizer import adamw

    tr = Trainer(lambda p, b: jnp.sum((b @ p["w"]) ** 2), adamw(1e-3),
                 {"w": jnp.ones((4, 4))}, TrainerConfig(log_every=10**9))
    feed = itertools.repeat(jnp.ones((3, 4)))
    tr.fit(feed, max_steps=1)                       # compile outside the session
    events = _profiled(lambda: tr.fit(feed, max_steps=4), tmp_path)
    steps = _named(events, "train.step")
    dispatch, sync = _named(events, "train.dispatch"), _named(events, "train.sync")
    assert len(steps) == len(dispatch) == len(sync) == 3
    for step, d, s in zip(steps, dispatch, sync):
        assert _inside(d, step) and _inside(s, step)
        assert d[2] <= s[1]                         # dispatch, then sync


def _tiny_coin_gcn(backend: str):
    from repro.core.quant import QuantConfig
    from repro.dist.policy import NO_POLICY
    from repro.graph.structure import blocked_adjacency
    from repro.launch.steps import gnn_loss_fn
    from repro.models.gcn import GCNConfig, gcn_init
    from repro.train.loop import Trainer
    from repro.train.optimizer import adamw

    n, e, f, c = 300, 1200, 40, 5
    rng = np.random.default_rng(0)
    s = np.concatenate([rng.integers(0, n, e), np.arange(n)]).astype(np.int32)
    r = np.concatenate([rng.integers(0, n, e), np.arange(n)]).astype(np.int32)
    w = np.full(s.shape, 0.1, np.float32)
    batch = {"feats": rng.random((n, f), dtype=np.float32), "senders": s, "receivers": r,
             "edge_weight": w, "labels": rng.integers(0, c, n).astype(np.int32),
             "label_mask": np.ones(n, np.float32)}
    if backend == "bsr":
        ba = blocked_adjacency(n, np.stack([s, r]), w)
        batch.update(bsr_vals=ba.block_vals, bsr_cols=ba.block_cols, bsr_lens=ba.row_nnzb)
    cfg = GCNConfig(layer_dims=(f, 16, c), backend=backend,
                    quant=QuantConfig(weight_bits=4, act_bits=4, act_percentile=99.9))
    tr = Trainer(gnn_loss_fn("coin_gcn", cfg, NO_POLICY), adamw(1e-3),
                 gcn_init(jax.random.PRNGKey(0), cfg))
    return tr, jax.device_put(batch)


@pytest.mark.parametrize("backend", ["segment", "bsr"])
def test_compiled_calibration_sort_carries_the_scope(backend):
    """The calibration sorts no more; each counting pass of its selection
    (one variadic ``reduce``) carries the scope and keeps its name."""
    from repro.core import quant

    tr, batch = _tiny_coin_gcn(backend)
    text = tr._step_fn.lower(tr.params, tr.opt_state, None, batch).compile().as_text()
    assert not re.search(r"= \S+ sort\(", text)
    passes = [line for line in text.splitlines()                  # lax.reduce's own name
              if re.match(r"\s*(?:ROOT )?%\S+ = .*? reduce\(", line)
              and re.search(r'op_name="[^"]*/reduce"', line)]
    assert len(passes) == 2 * math.ceil(31 / quant._DIGIT_BITS)   # per layer's activations
    for line in passes:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.split(r"[/()]", op_name).count("quant.calibrate") == 1, op_name
        assert re.match(r"\s*(?:ROOT )?%reduce(\.\d+)? = ", line)  # the instruction keeps its name
