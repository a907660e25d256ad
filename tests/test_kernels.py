"""Per-kernel allclose vs the ref.py oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph.structure import (
    blocked_adjacency,
    locality_block_order,
    permute_edge_index,
    relocate_rows,
    restore_rows,
)
from repro.kernels.ops import bsr_spmm, flash_attention, fm_interaction, fused_gcn_layer
from repro.kernels.ref import (
    bsr_spmm_ref,
    flash_attention_ref,
    fm_interaction_ref,
    fused_gcn_layer_ref,
)

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ bsr_spmm
@pytest.mark.parametrize("n,e,f", [(300, 900, 64), (1000, 5000, 96), (257, 800, 128)])
def test_bsr_spmm_matches_ref(n, e, f):
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    z = jnp.asarray(RNG.standard_normal((ba.n_padded, f)), jnp.float32)
    out = bsr_spmm(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), z)
    ref = bsr_spmm_ref(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bsr_spmm_equals_segment_sum():
    """The kernel computes the same aggregation as the segment-op reference
    path used by the models — ties the Pallas layer to the system layer."""
    from repro.graph.ops import aggregate

    n, e, f = 500, 2500, 64
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    z = jnp.asarray(RNG.standard_normal((ba.n_padded, f)), jnp.float32)
    out = bsr_spmm(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), z)[:n]
    seg = aggregate(z[:n], jnp.asarray(ei[0]), jnp.asarray(ei[1]), n, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(seg), rtol=3e-4, atol=3e-4)


@settings(max_examples=10, deadline=None)
@given(
    nb=st.integers(1, 4),
    t=st.integers(1, 5),
    f=st.sampled_from([128, 256]),
    seed=st.integers(0, 99),
)
def test_bsr_spmm_hypothesis_blocks(nb, t, f, seed):
    """Random block structures (including repeated columns = padding)."""
    r = np.random.default_rng(seed)
    B = 128
    vals = r.standard_normal((nb, t, B, B)).astype(np.float32) * 0.1
    cols = r.integers(0, nb, size=(nb, t)).astype(np.int32)
    z = jnp.asarray(r.standard_normal((nb * B, f)), jnp.float32)
    out = bsr_spmm(jnp.asarray(vals), jnp.asarray(cols), z, f_tile=128)
    ref = bsr_spmm_ref(jnp.asarray(vals), jnp.asarray(cols), z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_bsr_spmm_ragged_skips_padding_tiles():
    """The pl.when(t < lens[r]) guard really skips padded tiles: poison the
    tiles past each row's length with garbage — the ragged kernel must be
    unaffected (a dense-T kernel would fold the garbage in)."""
    r = np.random.default_rng(3)
    B, nb, T = 128, 3, 4
    vals = r.standard_normal((nb, T, B, B)).astype(np.float32) * 0.1
    cols = r.integers(0, nb, size=(nb, T)).astype(np.int32)
    lens = np.array([1, 3, 2], np.int32)
    clean = vals.copy()
    for rr in range(nb):
        clean[rr, lens[rr]:] = 0.0                       # the layout contract
        vals[rr, lens[rr]:] = 1e6                        # poison the padding
    z = jnp.asarray(r.standard_normal((nb * B, 128)), jnp.float32)
    out = bsr_spmm(jnp.asarray(vals), jnp.asarray(cols), z, lens=jnp.asarray(lens))
    ref = bsr_spmm_ref(jnp.asarray(clean), jnp.asarray(cols), z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_bsr_spmm_row_pad_wrapper():
    """z rows not a multiple of 128 are padded inside the wrapper."""
    n, e, f = 300, 1200, 64
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    z = jnp.asarray(RNG.standard_normal((n, f)), jnp.float32)   # unpadded rows
    vals, cols, lens = ba.arrays()
    out = bsr_spmm(vals, cols, z, lens=lens)
    zp = jnp.pad(z, ((0, ba.n_col_padded - n), (0, 0)))
    ref = bsr_spmm_ref(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), zp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ fused_gcn_layer
@pytest.mark.parametrize("order", ["feature_first", "aggregation_first"])
@pytest.mark.parametrize("relu", [True, False])
def test_fused_gcn_layer_matches_ref(order, relu):
    """One pallas_call == the unfused matmul ∘ SpMM ∘ bias ∘ act pipeline,
    at awkward widths (F_in/F_out not 128 multiples, ragged tail block)."""
    n, e, d_in, d_out = 300, 1500, 50, 7
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    x = jnp.asarray(RNG.standard_normal((n, d_in)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((d_in, d_out)) * 0.2, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(d_out), jnp.float32)
    out = fused_gcn_layer(*ba.arrays(), x, W, b, order=order, relu=relu)[:n]
    xp = jnp.pad(x, ((0, ba.n_col_padded - n), (0, 0)))
    ref = fused_gcn_layer_ref(
        jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), xp, W, b,
        order=order, relu=relu,
    )[:n]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_fused_gcn_layer_bf16_fp32_accumulation():
    """bf16 vals/features with fp32 accumulation: output within bf16 noise of
    the fp32 oracle, and the output dtype follows the inputs."""
    n, e, d_in, d_out = 256, 1200, 32, 16
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    vals, cols, lens = ba.arrays()
    x = jnp.asarray(RNG.standard_normal((n, d_in)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((d_in, d_out)) * 0.2, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(d_out), jnp.float32)
    out = fused_gcn_layer(
        vals.astype(jnp.bfloat16), cols, lens, x.astype(jnp.bfloat16),
        W.astype(jnp.bfloat16), b, order="feature_first", relu=True,
    )[:n]
    assert out.dtype == jnp.bfloat16
    ref = fused_gcn_layer_ref(vals, cols, jnp.pad(x, ((0, ba.n_col_padded - n), (0, 0))),
                              W, b, order="feature_first", relu=True)[:n]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


def test_fused_gcn_layer_grad_matches_ref():
    """The custom VJP (blocked-transpose scatter-add) == autodiff of the
    unfused oracle, for every differentiable operand."""
    n, e, d_in, d_out = 260, 1000, 24, 5
    ei = RNG.integers(0, n, size=(2, e)).astype(np.int32)
    w = RNG.standard_normal(e).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    vals, cols, lens = ba.arrays()
    x = jnp.asarray(RNG.standard_normal((n, d_in)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((d_in, d_out)) * 0.2, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(d_out), jnp.float32)
    pad = ba.n_col_padded - n
    for order in ("feature_first", "aggregation_first"):
        def loss_k(W, b, x, vals):
            return (fused_gcn_layer(vals, cols, lens, x, W, b, order=order)[:n] ** 2).sum()

        def loss_r(W, b, x, vals):
            xp = jnp.pad(x, ((0, pad), (0, 0)))
            return (fused_gcn_layer_ref(vals, cols, xp, W, b, order=order)[:n] ** 2).sum()

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(W, b, x, vals)
        gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(W, b, x, vals)
        # dvals: the ragged kernel does not read padding tiles, so its true
        # gradient there is zero; the dense-T oracle multiplies them. Compare
        # on the valid tiles (and check the kernel's padding grads ARE zero).
        tile_ok = (np.arange(ba.max_nnzb)[None, :] < ba.row_nnzb[:, None])
        assert np.all(np.asarray(gk[3])[~tile_ok] == 0.0)
        gk = (*gk[:3], jnp.asarray(np.asarray(gk[3]) * tile_ok[:, :, None, None]))
        gr = (*gr[:3], jnp.asarray(np.asarray(gr[3]) * tile_ok[:, :, None, None]))
        for name, a, r in zip(("dW", "db", "dx", "dvals"), gk, gr):
            scale = float(jnp.abs(r).max()) + 1e-9
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(r) / scale, rtol=2e-5, atol=2e-5,
                err_msg=f"{order}/{name}",
            )


# --------------------------------------------- ragged layout + reorder props
@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(100, 700),
    e=st.integers(50, 3000),
    seed=st.integers(0, 99),
)
def test_ragged_blocked_adjacency_invariants(n, e, seed):
    """Layout contract of the ragged BSR (docs/kernels.md): lens ≤ T, every
    tile past a row's length is a zero tile with a repeated in-range col id,
    and the locality permutation round-trips node arrays exactly."""
    r = np.random.default_rng(seed)
    ei = r.integers(0, n, size=(2, e)).astype(np.int32)
    w = (np.abs(r.standard_normal(e)) + 0.1).astype(np.float32)
    ba = blocked_adjacency(n, ei, w, block=128)
    T = ba.max_nnzb
    assert ba.row_nnzb.shape == (ba.n_block_rows,)
    assert (ba.row_nnzb <= T).all() and (ba.row_nnzb >= 0).all()
    assert ba.nnz_blocks == int(ba.row_nnzb.sum())
    assert 0.0 <= ba.padded_tile_fraction < 1.0
    assert (ba.block_cols >= 0).all() and (ba.block_cols < ba.n_block_cols).all()
    for rr in range(ba.n_block_rows):
        ln = int(ba.row_nnzb[rr])
        assert np.all(ba.block_vals[rr, ln:] == 0.0), "pad tiles must be zero"
        if 0 < ln < T:
            assert np.all(ba.block_cols[rr, ln:] == ba.block_cols[rr, ln - 1])
        # valid tiles: at least one nonzero entry each (they exist by def)
        for t in range(ln):
            assert np.any(ba.block_vals[rr, t] != 0.0)
    # permutation round-trip: restore ∘ relocate == id, and the permuted
    # graph's blocked aggregation equals the original after restore
    perm = locality_block_order(n, ei, block=128)
    assert np.array_equal(np.sort(perm), np.arange(n))
    x = r.standard_normal((n, 3)).astype(np.float32)
    np.testing.assert_array_equal(restore_rows(perm, relocate_rows(perm, x)), x)
    ei_p = permute_edge_index(perm, ei)
    # relabeling round-trip: mapping the new ids back through perm gives the
    # original endpoints (perm[inv[v]] == v)
    assert np.array_equal(perm[ei_p], ei.astype(np.int64))
    ba_p = blocked_adjacency(n, ei_p, w, block=128)
    z = r.standard_normal((n, 8)).astype(np.float32)
    zp = np.zeros((ba_p.n_col_padded, 8), np.float32)
    zp[:n] = relocate_rows(perm, z)
    agg_p = np.asarray(bsr_spmm_ref(*[jnp.asarray(a) for a in (ba_p.block_vals, ba_p.block_cols)], jnp.asarray(zp)))[:n]
    z0 = np.zeros((ba.n_col_padded, 8), np.float32)
    z0[:n] = z
    agg_0 = np.asarray(bsr_spmm_ref(jnp.asarray(ba.block_vals), jnp.asarray(ba.block_cols), jnp.asarray(z0)))[:n]
    np.testing.assert_allclose(restore_rows(perm, agg_p), agg_0, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ fm_interaction
@pytest.mark.parametrize("b,f,d", [(32, 13, 10), (256, 39, 10), (64, 8, 16)])
def test_fm_matches_ref_and_pairwise(b, f, d):
    emb = jnp.asarray(RNG.standard_normal((b, f, d)), jnp.float32)
    out = fm_interaction(emb)
    ref = fm_interaction_ref(emb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
    # explicit O(F²) pairwise oracle
    pair = 0.5 * (
        jnp.einsum("bfd,bgd->b", emb, emb) - jnp.einsum("bfd,bfd->b", emb, emb)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(pair), rtol=1e-3, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(
    b=st.sampled_from([8, 64, 200]),
    f=st.integers(2, 40),
    d=st.sampled_from([4, 10, 32]),
    seed=st.integers(0, 99),
)
def test_fm_hypothesis(b, f, d, seed):
    r = np.random.default_rng(seed)
    emb = jnp.asarray(r.standard_normal((b, f, d)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fm_interaction(emb)), np.asarray(fm_interaction_ref(emb)),
        rtol=2e-4, atol=2e-4,
    )


# ----------------------------------------------------------- flash_attention
@pytest.mark.parametrize("s,d,window", [(128, 64, None), (256, 64, 48), (128, 128, 16)])
def test_flash_matches_ref(s, d, window):
    q = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.float32)
    out = flash_attention(q, k, v, window=window, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    s, d = 128, 64
    q = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((2, s, d)), jnp.bfloat16)
    out = flash_attention(q, k, v, bq=64, bk=64)
    ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2
    )


def test_flash_matches_model_attention():
    """Kernel == the chunked-jnp attention the models actually run on CPU."""
    from repro.nn.attention import _chunked_attention

    s, d = 128, 64
    q = jnp.asarray(RNG.standard_normal((2, s, 4, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, s, 4, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, s, 4, d)), jnp.float32)
    model_out = _chunked_attention(q, k, v, jnp.arange(s), 32, chunk=64)
    qf = q.transpose(0, 2, 1, 3).reshape(8, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(8, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(8, s, d)
    kern = flash_attention(qf, kf, vf, window=32, bq=64, bk=64)
    kern = kern.reshape(2, 4, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(model_out), rtol=3e-5, atol=3e-5)


# ------------------------------------------------------- interpret vs native
def test_auto_is_native_whenever_the_backend_is_tpu(monkeypatch):
    """On a TPU backend the wrappers never fall back to interpret mode —
    not by default, and not on request; on the CPU interpret is the
    default."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops._auto(None) is False
    assert ops._auto(False) is False
    with pytest.raises(ValueError, match="CPU only"):
        ops._auto(True)
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    assert ops._auto(None) is True
