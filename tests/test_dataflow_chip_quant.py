"""Dataflow reordering (§IV-C3), chip capacity (§V-C), quantization (§V-B)."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.chip import ChipModel, chips_required
from repro.core.dataflow import (
    choose_order,
    dense_multiply_count,
    exchange_cost,
    sparse_multiply_count,
)
from repro.core.quant import (
    QuantConfig,
    dequantize_payload,
    fake_quant,
    kth_largest,
    payload_bits,
    quantize_payload,
    quantize_tree,
)


def test_nell_311x_reduction():
    """§IV-C3 verbatim: 2.3e13 vs 7.4e10 multiplies, ≈311× reduction."""
    c = dense_multiply_count(65755, 5414, 16)
    assert np.isclose(c.aggregation_first, 2.3e13, rtol=0.03)
    assert np.isclose(c.feature_first, 7.4e10, rtol=0.02)
    assert 300 < c.reduction < 320
    assert c.best == "feature_first"


def test_chooser_flips_when_widths_flip():
    assert choose_order(1000, d_in=512, d_out=16) == "feature_first"
    assert choose_order(1000, d_in=16, d_out=512) == "aggregation_first"
    assert choose_order(1000, 512, 16, n_edges=5000) == "feature_first"


@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(10, 10_000),
    e=st.integers(10, 100_000),
    d_in=st.integers(1, 2048),
    d_out=st.integers(1, 2048),
)
def test_chooser_optimal_under_both_cost_models(n, e, d_in, d_out):
    dc = dense_multiply_count(n, d_in, d_out)
    sc = sparse_multiply_count(n, e, d_in, d_out)
    assert dc.best == min(
        ("aggregation_first", dc.aggregation_first), ("feature_first", dc.feature_first),
        key=lambda kv: kv[1],
    )[0] or dc.aggregation_first == dc.feature_first
    assert sc.reduction > 0


def test_chip_counts_match_paper_where_derivable():
    cm = ChipModel()
    table = {
        "cora": (2708, [1433, 16, 7]),
        "citeseer": (3327, [3703, 16, 6]),
        "pubmed": (19717, [500, 16, 3]),
        "nell": (65755, [5414, 16, 210]),
    }
    # crossbar-granular reproduces Cora/Citeseer (1) and Nell (45) exactly.
    assert chips_required(cm, *table["cora"]) == 1
    assert chips_required(cm, *table["citeseer"]) == 1
    assert chips_required(cm, *table["nell"]) == 45
    # cell-granular reproduces Pubmed ≈ 3 (paper rounds 3.09 down; we ceil).
    assert chips_required(cm, *table["pubmed"], mode="cell") in (3, 4)
    # 30 MB chip (§IV-B3).
    assert abs(cm.bytes_per_chip - 30 * 2**20) / (30 * 2**20) < 0.01


def test_chips_monotone_in_nodes():
    cm = ChipModel()
    prev = 0
    for n in [1000, 5000, 20_000, 60_000, 120_000]:
        c = chips_required(cm, n, [128, 16, 4])
        assert c >= prev
        prev = c


def test_fake_quant_level_count_and_ste():
    x = jnp.linspace(-1, 1, 1001)
    for bits in [2, 3, 4, 8]:
        q = fake_quant(x, bits)
        assert len(np.unique(np.asarray(q))) <= 2**bits
    # straight-through: gradient of sum(fake_quant(x)) is all-ones, with a
    # percentile scale too
    for p in (None, 99.0, 99.9):
        g = jax.grad(lambda x: fake_quant(x, 4, percentile=p).sum())(x)
        np.testing.assert_array_equal(np.asarray(g), np.ones(x.shape, np.float32))
    # ≥32 bits is a no-op
    assert np.array_equal(np.asarray(fake_quant(x, 32)), np.asarray(x))


@settings(max_examples=25, deadline=None)
@given(bits=st.integers(2, 8), seed=st.integers(0, 1000))
def test_fake_quant_error_bound(bits, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(256), jnp.float32)
    q = fake_quant(x, bits)
    amax = float(jnp.max(jnp.abs(x)))
    step = amax / (2 ** (bits - 1) - 1)
    assert float(jnp.max(jnp.abs(q - x))) <= step * 0.5 + 1e-6


def test_fake_quant_percentile_clips_small_tensor_outlier():
    """Regression (ISSUE 6 satellite 1): the nearest-rank percentile must
    still clip on SMALL tensors. The old ``int(n·(1−p/100))`` floored to 0
    for n < 1/(1−p/100) (e.g. n=100 at p=99), silently degrading to amax —
    one outlier then owned the whole calibration range."""
    x = np.zeros(100, np.float32)
    x[:99] = np.linspace(-1.0, 1.0, 99)
    x[99] = 50.0                                     # the outlier
    q99 = np.asarray(fake_quant(jnp.asarray(x), 4, percentile=99.0))
    # nearest-rank: p=99, n=100 → k = 100 − ceil(99) + 1 = 2 → scale from the
    # 2nd-largest magnitude (1.0), NOT the outlier. Code points cover [-1, 1]:
    # the quantized inliers stay tight and the outlier saturates at ≈ -qmin·step.
    step = 1.0 / 7.0
    inlier_err = np.abs(q99[:99] - x[:99]).max()
    assert inlier_err <= step * 0.5 + 1e-6
    assert q99[99] <= 8 * step + 1e-6               # clipped, nowhere near 50
    # pure-amax scale for contrast: inliers collapse onto ~1 code point
    q_amax = np.asarray(fake_quant(jnp.asarray(x), 4))
    assert np.abs(q_amax[:99] - x[:99]).max() > 10 * inlier_err


def test_fake_quant_percentile_degrades_to_amax_when_rank_saturates():
    """n=50 at p=99: ceil(0.99·50)=50 → k=1 — the percentile IS the max
    (documented nearest-rank behavior, not the old silent floor-to-zero)."""
    x = np.linspace(-1.0, 1.0, 49).astype(np.float32)
    x = np.concatenate([x, [20.0]]).astype(np.float32)
    q = np.asarray(fake_quant(jnp.asarray(x), 4, percentile=99.0))
    q_amax = np.asarray(fake_quant(jnp.asarray(x), 4))
    np.testing.assert_array_equal(q, q_amax)


def test_quantize_tree_threads_percentile():
    """quantize_tree(percentile=) must reach every leaf's calibration (it was
    silently dropped before — tree-level quantization always ran pure-amax)."""
    x = np.zeros(100, np.float32)
    x[:99] = np.linspace(-1.0, 1.0, 99)
    x[99] = 50.0
    tree = {"a": jnp.asarray(x), "n": 3}
    out = quantize_tree(tree, 4, percentile=99.0)
    ref = np.asarray(fake_quant(jnp.asarray(x), 4, percentile=99.0))
    np.testing.assert_array_equal(np.asarray(out["a"]), ref)
    assert out["n"] == 3
    out_amax = quantize_tree(tree, 4)
    assert not np.array_equal(np.asarray(out_amax["a"]), ref)


def _rank_k(n, p):
    return min(n, max(1, n - math.ceil(p / 100.0 * n) + 1))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _fake_quant_top_k(x, bits, p):
    """`fake_quant` with the percentile scale taken from `lax.top_k`."""
    qmax = float(2 ** (bits - 1) - 1)
    mag = jnp.abs(x)
    amax = jax.lax.top_k(mag.reshape(-1), _rank_k(mag.size, p))[0][-1]
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    return x + jax.lax.stop_gradient(q - x)


# Both sides jitted: XLA may rewrite ``x / scale`` in either.
_kth_largest = jax.jit(kth_largest, static_argnums=1)
_fake_quant = jax.jit(fake_quant, static_argnums=(1, 2))


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


def _assert_selection_exact(x, p):
    """The selection's scale and `fake_quant`'s whole output, bit for bit
    against `lax.top_k` and a nearest rank of `np.sort`."""
    x = np.asarray(x, np.float32)
    n = x.size
    mag = jnp.abs(jnp.asarray(x))
    amax = _kth_largest(mag, _rank_k(n, p))
    top_k = jax.lax.top_k(mag.reshape(-1), _rank_k(n, p))[0][-1]
    nearest = np.sort(np.abs(x).ravel())[math.ceil(p / 100.0 * n) - 1]
    assert amax.dtype == jnp.float32
    assert _bits(amax) == _bits(top_k) == _bits(nearest), (float(amax), float(top_k), nearest)
    out = _fake_quant(jnp.asarray(x), 4, p)
    np.testing.assert_array_equal(_bits(out), _bits(_fake_quant_top_k(jnp.asarray(x), 4, p)))


def _case(name, rng):
    if name == "n1":
        return np.array([-2.5], np.float32)
    if name == "k1":                                # n < 1/(1 − p/100): the rank is the max
        return np.concatenate([np.linspace(-1, 1, 49), [20.0]]).astype(np.float32)
    if name == "all_zero":                          # amax 0: the scale falls back to 1
        return np.zeros((40, 25), np.float32)
    if name == "zeros_99":                          # like nell's 32 nonzeros in 5414
        x = rng.standard_normal((120, 90)).astype(np.float32)
        return np.where(rng.random(x.shape) < 0.99, 0.0, x).astype(np.float32)
    if name == "ties":
        return (np.round(rng.standard_normal((64, 37)) * 2) / 2).astype(np.float32)
    if name == "denormal":
        x = (rng.standard_normal(3000) * 1e-39).astype(np.float32)
        assert np.any((x != 0) & (np.abs(x) < np.finfo(np.float32).tiny))
        return x
    if name == "signed_zero":
        x = np.where(rng.random(2000) < 0.5, 0.0, -0.0).astype(np.float32)
        x[:3] = [1.5, -0.25, 3.0]
        return x
    if name == "inf":
        x = rng.standard_normal((50, 60)).astype(np.float32)
        x.flat[rng.choice(x.size, 4, replace=False)] = [np.inf, -np.inf, np.inf, -np.inf]
        return x
    if name == "inf_owns_rank":                     # the k-th largest is +inf itself
        return np.array([np.inf, -np.inf, 1.0, -3.0], np.float32)
    if name == "two_d":
        return (rng.standard_normal((300, 17)) * rng.lognormal(size=(300, 1))).astype(np.float32)
    raise KeyError(name)


@pytest.mark.parametrize("p", [99.0, 99.9])
@pytest.mark.parametrize("name", ["n1", "k1", "all_zero", "zeros_99", "ties", "denormal",
                                  "signed_zero", "inf", "inf_owns_rank", "two_d"])
def test_kth_largest_matches_top_k_and_sort_bit_for_bit(name, p):
    _assert_selection_exact(_case(name, np.random.default_rng(7)), p)


@settings(max_examples=40, deadline=None)
@given(
    x=hnp.arrays(np.float32, st.sampled_from([(1,), (9,), (150,), (1200,), (40, 35)]),
                 elements=st.floats(width=32, allow_nan=False)),
    p=st.sampled_from([99.0, 99.9]),
)
def test_kth_largest_matches_top_k_and_sort_on_any_floats(x, p):
    _assert_selection_exact(x, p)


def test_kth_largest_keeps_a_narrower_float():
    mag = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (70, 30), jnp.bfloat16))
    got = _kth_largest(mag, 5)
    assert got.dtype == jnp.bfloat16
    assert got == jax.lax.top_k(mag.reshape(-1), 5)[0][-1]


# --------------------------------------------------------- halo wire payloads
def test_payload_bits_table_and_unknown():
    assert payload_bits(None) == payload_bits("fp32") == 32
    assert payload_bits("bf16") == 16
    assert payload_bits("int8") == 8
    with pytest.raises(ValueError, match="unknown halo payload"):
        payload_bits("fp8")


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 500))
def test_payload_roundtrip_error_bounds(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)
    w, s = quantize_payload(x, "fp32")
    assert s is None and np.array_equal(np.asarray(w), np.asarray(x))
    w, s = quantize_payload(x, "bf16")
    assert s is None and w.dtype == jnp.bfloat16
    back = np.asarray(dequantize_payload(w, s))
    # bf16: 8 mantissa bits → ≤ 2^-8 relative per element
    assert np.abs(back - np.asarray(x)).max() <= 2.0**-8 * np.abs(x).max() + 1e-7
    w, s = quantize_payload(x, "int8")
    assert w.dtype == jnp.int8 and s.shape == (1, 1)
    back = np.asarray(dequantize_payload(w, s))
    amax = float(np.abs(np.asarray(x)).max())
    assert np.abs(back - np.asarray(x)).max() <= amax / 127.0 * 0.5 + 1e-6


def test_int8_payload_multiblock_dequant_uses_per_sender_scale():
    """dequantize_payload with (n_blocks, 1) scales rescales each gathered
    export block by ITS sender's amax — mixing magnitudes across senders."""
    small = np.full((4, 3), 0.5, np.float32)
    big = np.full((4, 3), 100.0, np.float32)
    w1, s1 = quantize_payload(jnp.asarray(small), "int8")
    w2, s2 = quantize_payload(jnp.asarray(big), "int8")
    wire = jnp.concatenate([w1, w2], axis=0)
    scales = jnp.concatenate([s1, s2], axis=0)      # (2, 1)
    back = np.asarray(dequantize_payload(wire, scales))
    np.testing.assert_allclose(back[:4], small, atol=0.5 / 127 + 1e-6)
    np.testing.assert_allclose(back[4:], big, atol=100.0 / 127 + 1e-4)


def test_exchange_cost_model():
    ec = exchange_cost(1000, 64, 32, 0.0)
    assert ec.wire_bytes == 1000 * 64 * 4 and ec.exposed_bytes == ec.wire_bytes
    assert ec.compression == 1.0
    ec = exchange_cost(1000, 64, 16, 0.75)
    assert ec.wire_bytes == 1000 * 64 * 2          # bf16 halves the wire
    assert ec.exposed_bytes == pytest.approx(ec.wire_bytes * 0.25)
    assert ec.compression == 2.0
    assert exchange_cost(1000, 64, 8).compression == 4.0
    # overlap=1 → nothing exposed
    assert exchange_cost(10, 4, 32, 1.0).exposed_bytes == 0.0


@settings(max_examples=30, deadline=None)
@given(
    d_in=st.integers(1, 512),
    d_out=st.integers(1, 512),
    halo_rows=st.integers(0, 5000),
    bits=st.sampled_from([8, 16, 32]),
    ov=st.floats(0.0, 1.0),
)
def test_choose_order_argmax_invariant_under_exchange_term(d_in, d_out, halo_rows, bits, ov):
    """The exchange term moves with the same d_out-vs-d_in sign as compute,
    so adding it never flips the chooser (documented on choose_order)."""
    base = choose_order(2000, d_in, d_out, n_edges=10_000)
    with_exchange = choose_order(
        2000, d_in, d_out, n_edges=10_000,
        halo_rows=halo_rows, payload_bits=bits, overlap_fraction=ov,
    )
    assert with_exchange == base
