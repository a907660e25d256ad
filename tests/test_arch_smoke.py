"""Deliverable (f): per-architecture smoke tests.

For each of the 10 assigned architectures (+ the paper's coin_gcn):
instantiate the REDUCED config, run one forward AND one train step on CPU,
assert output shapes and no NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_arch

KEY = jax.random.PRNGKey(0)


def _finite(x) -> bool:
    return bool(jnp.all(jnp.isfinite(x)))


def _tiny_graph(n=40, e=160, d_in=8, seed=0):
    r = np.random.default_rng(seed)
    s = r.integers(0, n, e).astype(np.int32)
    d = (s + 1 + r.integers(0, n - 1, e)).astype(np.int32) % n
    return (
        jnp.asarray(r.standard_normal((n, d_in)), jnp.float32),
        jnp.asarray(s),
        jnp.asarray(d),
        jnp.asarray(r.standard_normal((n, 3)), jnp.float32),
    )


@pytest.mark.parametrize("arch_id", [a for a in ALL_ARCHS if get_arch(a).family == "lm"])
def test_lm_smoke(arch_id):
    from repro.models.transformer_lm import lm_forward, lm_init, lm_loss
    from repro.train.optimizer import adam

    spec = get_arch(arch_id)
    cfg = spec.make_reduced()
    params = lm_init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    logits, aux = lm_forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab)
    assert _finite(logits) and _finite(aux)
    # one train step
    opt = adam(1e-3)
    state = opt.init(params)
    loss, grads = jax.value_and_grad(lm_loss)(params, toks, cfg)
    params2, _ = opt.update(grads, state, params)
    assert _finite(loss)
    loss2 = lm_loss(params2, toks, cfg)
    assert _finite(loss2)


@pytest.mark.parametrize("arch_id", ["egnn", "pna", "graphcast", "equiformer-v2"])
def test_gnn_smoke(arch_id):
    from repro.train.optimizer import adam

    spec = get_arch(arch_id)
    cfg = spec.make_reduced()
    feats, s, r, pos = _tiny_graph(d_in=getattr(cfg, "d_in", 8) or 8)
    n = feats.shape[0]

    if arch_id == "egnn":
        from repro.models.egnn import egnn_forward as fwd, egnn_init as init

        params = init(KEY, cfg)
        out, coords = fwd(params, feats, pos, s, r, cfg)
        assert out.shape == (n, cfg.d_out) and coords.shape == (n, 3)
        loss_fn = lambda p: jnp.mean(fwd(p, feats, pos, s, r, cfg)[0] ** 2)
    elif arch_id == "pna":
        from repro.models.pna import pna_forward as fwd, pna_init as init

        params = init(KEY, cfg)
        out = fwd(params, feats, s, r, cfg)
        assert out.shape == (n, cfg.d_out)
        loss_fn = lambda p: jnp.mean(fwd(p, feats, s, r, cfg) ** 2)
    elif arch_id == "graphcast":
        from repro.models.graphcast import graphcast_forward as fwd, graphcast_graph, graphcast_init as init

        graph = {k: jnp.asarray(v) for k, v in graphcast_graph(cfg).arrays().items()}
        n_grid = graph["grid_nodes"].shape[0]
        b = dict(graph, grid_inputs=jax.random.normal(KEY, (n_grid, cfg.d_grid_in)))
        params = init(KEY, cfg)
        out = jax.jit(fwd, static_argnums=2)(params, b, cfg)
        assert out.shape == (n_grid, cfg.n_vars)
        loss_fn = jax.jit(lambda p: jnp.mean(fwd(p, b, cfg) ** 2))
    else:
        from repro.models.equiformer_v2 import equiformer_forward as fwd, equiformer_init as init

        params = init(KEY, cfg)
        out = fwd(params, feats, pos, s, r, cfg)
        assert out.shape == (n, cfg.d_out)
        loss_fn = lambda p: jnp.mean(fwd(p, feats, pos, s, r, cfg) ** 2)

    assert _finite(out)
    opt = adam(1e-3)
    state = opt.init(params)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    params2, _ = opt.update(grads, state, params)
    assert _finite(loss) and _finite(loss_fn(params2))


def test_deepfm_smoke():
    from repro.models.deepfm import deepfm_forward, deepfm_init, deepfm_loss, deepfm_retrieval
    from repro.train.optimizer import adam

    spec = get_arch("deepfm")
    cfg = spec.make_reduced()
    params = deepfm_init(KEY, cfg)
    ids = jax.random.randint(KEY, (32, cfg.n_fields), 0, cfg.rows_per_field)
    logits = deepfm_forward(params, ids, cfg)
    assert logits.shape == (32,) and _finite(logits)
    labels = (jax.random.uniform(KEY, (32,)) > 0.5).astype(jnp.float32)
    opt = adam(1e-3)
    state = opt.init(params)
    loss, grads = jax.value_and_grad(deepfm_loss)(params, ids, labels, cfg)
    params2, _ = opt.update(grads, state, params)
    assert _finite(loss) and _finite(deepfm_loss(params2, ids, labels, cfg))
    scores = deepfm_retrieval(params, ids[:2], jax.random.randint(KEY, (2, 64), 0, cfg.rows_per_field), cfg)
    assert scores.shape == (2, 64) and _finite(scores)


def test_coin_gcn_smoke():
    from repro.models.gcn import gcn_forward, gcn_init

    spec = get_arch("coin_gcn")
    cfg = spec.make_reduced()
    feats, s, r, _ = _tiny_graph(d_in=cfg.layer_dims[0])
    w = jnp.ones_like(s, dtype=jnp.float32)
    params = gcn_init(KEY, cfg)
    out = gcn_forward(params, feats, s, r, w, cfg)
    assert out.shape == (feats.shape[0], cfg.layer_dims[-1])
    assert _finite(out)


def test_registry_covers_40_cells():
    cells = 0
    for a in ALL_ARCHS:
        if a == "coin_gcn":
            continue
        cells += len(get_arch(a).shapes)
    # graphcast has one shape, its published grid and mesh (era5_1deg)
    assert cells == 37
    # long_500k runs exactly for the sub-quadratic LM arch (gemma3).
    runnable_500k = [
        a for a in ALL_ARCHS
        if get_arch(a).family == "lm"
        and get_arch(a).shapes["long_500k"].skip_reason is None
    ]
    assert runnable_500k == ["gemma3-12b"]
