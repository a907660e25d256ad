"""Compile the main path for a described TPU v5e — no chip needed.

Interpret-mode Pallas (every other kernel test) cannot see what the chip's
compiler refuses: unaligned slices, too much VMEM, a program that does not
fit. These tests compile the kernels and the train steps at real widths for
a `v5e:2x2` topology that is described, not attached, and check that the
compiled program holds a native kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep these tests in this one file for the same reason.
The compiles keep the persistent compile cache off — an entry written for a
described chip cannot be read back without one.

The train steps at real widths compile with quantization off, which keeps
them to the layers' own path. The published config's 4-bit QAT calibration
is compiled by the last two tests: a small QAT step, and the selection at
nell's width.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# pubmed's blocked adjacency as the kernels see it: 155 block rows of 128,
# up to 147 nonzero tiles each.
R, T, B = 155, 147, 128
N_PUBMED, E_PUBMED = 19717, 88651


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs outside the repo
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Kernel wrappers pick native Pallas, as they do on a TPU backend."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _tables(sharding, dtype):
    return (_sds((R, T, B, B), dtype, sharding), _sds((R, T), jnp.int32, sharding),
            _sds((R,), jnp.int32, sharding))


def _fp32_spec():
    """coin_gcn's registry entry on the bsr backend with quantization off
    (see module doc)."""
    from repro.configs import get_arch
    from repro.core.quant import QuantConfig

    spec = get_arch("coin_gcn")
    return dataclasses.replace(spec, make_config=lambda shape=None: dataclasses.replace(
        spec.make_config(shape), backend="bsr", quant=QuantConfig(enabled=False)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("f", [128, 512])
def test_bsr_spmm_compiles_at_pubmed_width(one_chip, f, dtype):
    from repro.kernels.bsr_spmm import bsr_spmm_pallas

    z = _sds((R * B, f), dtype, one_chip)
    text = bsr_spmm_pallas.lower(
        *_tables(one_chip, dtype), z, f_tile=f, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("order,f_in", [
    ("feature_first", 512),       # pubmed (500 → 16), lanes padded
    ("feature_first", 8832),      # extcora (8710 → 16)
    ("aggregation_first", 512),
])
def test_fused_gcn_layer_compiles(one_chip, order, f_in, dtype):
    from repro.kernels.fused_gcn import fused_gcn_layer_pallas

    f_out = 128                   # 16 hidden units, padded to one lane tile
    args = (*_tables(one_chip, dtype), _sds((R * B, f_in), dtype, one_chip),
            _sds((f_in, f_out), dtype, one_chip), _sds((1, f_out), dtype, one_chip))
    text = fused_gcn_layer_pallas.lower(
        *args, order=order, relu=True, f_tile=f_out, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("edges,side,nodes", [
    ("mesh", "recv", "mesh"),     # the multimesh: 64 calls a step
    ("g2m", "send", "grid"),      # Grid2Mesh's senders: 510 node blocks
    ("m2g", "recv", "grid"),      # Mesh2Grid's receivers: the most edges
])
def test_sorted_sum_compiles_at_graphcast_width(one_chip, edges, side, nodes):
    """GraphCast_small's sorted sums at latent 512, their layouts read from
    the 1° geometry."""
    from repro.graph.sphere import graph_shapes
    from repro.kernels.sorted_sum import sorted_sum_pallas

    shapes = {k: shape for k, shape, _ in graph_shapes(1.0, 5, 2, 0.6)}
    x = _sds((shapes[f"{edges}_senders"][0], 512), jnp.float32, one_chip)
    tiles = _sds(shapes[f"{edges}_{side}_tiles"], jnp.int32, one_chip)
    pairs = _sds(shapes[f"{edges}_{side}_pairs"], jnp.int32, one_chip)
    text = sorted_sum_pallas.lower(x, tiles, pairs, shapes[f"{nodes}_nodes"][0],
                                   interpret=False).compile().as_text()
    assert "tpu_custom_call" in text and "scatter" not in text


def _compile_bsr_train_step(cfg, n, e, tables, sharding):
    """The jitted train step of ``coin_gcn`` on the bsr backend, compiled for
    an ``n``-node, ``e``-edge graph (self-loops included) with ``tables``
    (vals, cols, lens) as its blocked adjacency."""
    from repro.dist.policy import NO_POLICY
    from repro.launch.steps import gnn_loss_fn
    from repro.models.gcn import gcn_init
    from repro.train.loop import Trainer
    from repro.train.optimizer import adamw

    tr = Trainer(gnn_loss_fn("coin_gcn", cfg, NO_POLICY), adamw(1e-3),
                 gcn_init(jax.random.PRNGKey(0), cfg))
    vals, cols, lens = tables
    batch = {
        "feats": _sds((n, cfg.layer_dims[0]), jnp.float32, sharding),
        "senders": _sds((e,), jnp.int32, sharding),
        "receivers": _sds((e,), jnp.int32, sharding),
        "edge_weight": _sds((e,), jnp.float32, sharding),
        "labels": _sds((n,), jnp.int32, sharding),
        "label_mask": _sds((n,), jnp.float32, sharding),
        "bsr_vals": vals, "bsr_cols": cols, "bsr_lens": lens,
    }

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype, sharding), tree)

    return tr._step_fn.lower(
        abstract(tr.params), abstract(tr.opt_state), None, batch).compile()


def test_coin_gcn_bsr_train_step_compiles_at_pubmed_width(one_chip, native):
    from repro.dist.policy import NO_POLICY
    from repro.launch.steps import gnn_loss_fn
    from repro.models.gcn import gcn_init
    from repro.train.loop import Trainer
    from repro.train.optimizer import adamw

    spec = _fp32_spec()
    cfg = spec.make_config(spec.shapes["pubmed"])
    compiled = _compile_bsr_train_step(
        cfg, N_PUBMED, E_PUBMED + N_PUBMED, _tables(one_chip, jnp.float32), one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < 16e9


def test_flat_halo_train_step_compiles_on_four_chips(topo, native):
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell

    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    spec = _fp32_spec()
    cell = build_cell(spec, spec.shapes["pubmed"], mesh)
    assert cell.comm == "halo" and cell.halo_plan.k == 4 and cell.bsr_stats is not None
    text = cell.lower(mesh).compile().as_text()
    assert "tpu_custom_call" in text


def test_qat_bsr_train_step_keeps_kernel_names_and_names_its_calibration(one_chip, native):
    """The compiled QAT step calibrates without a sort, the ``quant.calibrate``
    scope reaches every counting pass of the selection, and every
    instruction name is left alone: the two forward fused kernels are still
    ``jvp_jit_fused_gcn_layer_pallas__``, the name the chip trace's kernel
    metrics match. A small graph, with the published 4-bit QAT on."""
    from repro.core import quant
    from repro.core.quant import QuantConfig
    from repro.models.gcn import GCNConfig

    r, t = 4, 3
    cfg = GCNConfig(layer_dims=(256, 16, 3), backend="bsr",
                    quant=QuantConfig(weight_bits=4, act_bits=4, act_percentile=99.9))
    tables = (_sds((r, t, B, B), jnp.float32, one_chip), _sds((r, t), jnp.int32, one_chip),
              _sds((r,), jnp.int32, one_chip))
    text = _compile_bsr_train_step(cfg, r * B - 20, 3000, tables, one_chip).as_text()
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*tpu_custom_call", text, re.M)
    assert len(kernels) == 2
    assert all(re.fullmatch(r"jvp_jit_fused_gcn_layer_pallas__(\.\d+)?", k) for k in kernels)
    assert not re.search(r" sort\(", text)
    # One fused read per pass, for each of the two percentile calibrations.
    passes = [line for line in text.splitlines()
              if re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = .* fusion\(", line)
              and re.search(r'op_name="[^"]*/reduce"', line)]
    assert len(passes) == 2 * math.ceil(31 / quant._DIGIT_BITS)
    assert all('op_name="jit(step)/jvp(quant.calibrate)/reduce"' in line for line in passes)


def test_percentile_calibration_at_nell_width_lowers_without_a_sort():
    """The selection at nell's f32[65755, 5414] layer-0 input: no ``top_k``,
    which XLA lowers to a full sort of every magnitude. Lowering only."""
    from repro.core.quant import fake_quant

    x = jax.ShapeDtypeStruct((65755, 5414), jnp.float32)
    lowered = jax.jit(lambda x: fake_quant(x, 4, percentile=99.9)).lower(x)
    assert "quant.calibrate" in lowered.as_text(debug_info=True)
    assert not re.search(r"sort|top_?k", lowered.as_text(), re.I)
