"""Core SSL machinery: losses, heads, MoCo v3 engine, momentum EMA."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SSLConfig
from repro.core import heads, losses, ssl as ssl_mod

VIT = ModelConfig("t-vit", "dense", 2, 64, 4, 4, 128, 0, causal=False,
                  compute_dtype="float32", act="gelu")
SSLC = SSLConfig(proj_hidden=64, pred_hidden=64, proj_dim=32)


def test_info_nce_identity_minimum(rng):
    """Loss is lowest when q == k (positives perfectly aligned)."""
    q = jax.random.normal(rng, (32, 16))
    perfect = losses.info_nce(q, q, 0.2)
    shuffled = losses.info_nce(q, jnp.roll(q, 1, axis=0), 0.2)
    assert perfect < shuffled


def test_info_nce_matches_manual(rng):
    q = jax.random.normal(rng, (8, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    qn = np.asarray(losses.l2_normalize(q))
    kn = np.asarray(losses.l2_normalize(k))
    logits = qn @ kn.T / 0.2
    want = np.mean([-logits[i, i] + np.log(np.sum(np.exp(logits[i])))
                    for i in range(8)])
    got = float(losses.info_nce(q, k, 0.2))
    assert abs(got - want) < 1e-5


def test_simclr_symmetric(rng):
    z1 = jax.random.normal(rng, (16, 8))
    z2 = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    assert abs(float(losses.simclr_nt_xent(z1, z2, 0.5))
               - float(losses.simclr_nt_xent(z2, z1, 0.5))) < 1e-5


def test_byol_regression_range(rng):
    q = jax.random.normal(rng, (16, 8))
    assert float(losses.byol_regression(q, q)) < 1e-6
    v = float(losses.byol_regression(q, -q))
    assert abs(v - 4.0) < 1e-5      # max distance for unit vectors


def test_heads_shapes(rng):
    p = heads.proj_init(rng, 64, 128, 32)
    x = jax.random.normal(rng, (8, 64))
    out = heads.head_apply(p, x)
    assert out.shape == (8, 32)
    q = heads.pred_init(rng, 32, 128, 32)
    assert heads.head_apply(q, out).shape == (8, 32)


@pytest.mark.parametrize("method", ["moco_v3", "simclr", "byol"])
def test_ssl_loss_finite_and_grads(method, rng):
    sc = dataclasses.replace(SSLC, method=method)
    enc = ssl_mod.make_vit_encoder(VIT)
    state = ssl_mod.ssl_init(rng, enc, sc)
    x1 = jax.random.normal(rng, (8, 32, 32, 3))
    x2 = x1 + 0.01

    def loss_fn(online):
        st = {**state, "online": online}
        return ssl_mod.ssl_loss(st, x1, x2, enc, sc)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(state["online"])
    assert jnp.isfinite(loss)
    assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads))


def test_momentum_update_ema(rng):
    enc = ssl_mod.make_vit_encoder(VIT)
    state = ssl_mod.ssl_init(rng, enc, SSLC)
    # perturb online; EMA must move target a (1-mu) fraction toward online
    online = jax.tree.map(lambda a: a + 1.0, state["online"])
    state = {**state, "online": online}
    new = ssl_mod.momentum_update(state, 0.9)
    t0 = jax.tree.leaves(state["target"])[0]
    t1 = jax.tree.leaves(new["target"])[0]
    o = jax.tree.leaves({"enc": online["enc"], "proj": online["proj"]})[0]
    assert jnp.allclose(t1, 0.9 * t0 + 0.1 * o, atol=1e-5)


def test_alignment_pulls_toward_global(rng):
    """With huge alignment weight the gradient is dominated by Eq. 3."""
    enc = ssl_mod.make_vit_encoder(VIT)
    state = ssl_mod.ssl_init(rng, enc, SSLC)
    x1 = jax.random.normal(rng, (8, 32, 32, 3))
    x2 = jax.random.normal(jax.random.PRNGKey(2), (8, 32, 32, 3))
    g_enc = jax.tree.map(lambda a: a * 1.1, state["online"]["enc"])
    l0, m0 = ssl_mod.ssl_loss(state, x1, x2, enc, SSLC,
                              global_enc=g_enc, align_weight=0.0)
    l1, m1 = ssl_mod.ssl_loss(state, x1, x2, enc, SSLC,
                              global_enc=g_enc, align_weight=0.01)
    assert "align" in m1 and "align" not in m0
    assert abs(float(l1 - l0 - 0.01 * m1["align"])) < 1e-4


def test_lm_ssl_loss_with_alignment(rng):
    cfg = ModelConfig("t", "dense", 2, 64, 4, 2, 128, 97,
                      compute_dtype="float32")
    from repro.models import lm as lm_mod
    params = lm_mod.init_lm(rng, cfg)
    tok = jax.random.randint(rng, (2, 32), 0, 97)
    loss, m = ssl_mod.lm_ssl_loss(params, {"tokens": tok, "labels": tok},
                                  cfg, sub_layers=2, active_from=1,
                                  global_params=params, align_weight=0.01)
    assert jnp.isfinite(loss) and "align" in m


def test_remat_encoder_matches_plain(rng):
    """``TrainConfig.remat`` reaches the ViT forward: per-block
    checkpointing recomputes activations but leaves the loss and the
    gradients as they were."""
    plain = ssl_mod.make_vit_encoder(VIT)
    remat = ssl_mod.make_vit_encoder(VIT, remat=True)
    state = ssl_mod.ssl_init(rng, plain, SSLC)
    x1, x2 = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 32, 32, 3))

    def loss_and_grads(enc):
        def f(online):
            return ssl_mod.ssl_loss({**state, "online": online}, x1, x2,
                                    enc, SSLC)[0]
        return jax.jit(jax.value_and_grad(f))(state["online"])

    (l0, g0), (l1, g1) = loss_and_grads(plain), loss_and_grads(remat)
    assert abs(float(l0) - float(l1)) <= 1e-6 * abs(float(l0))
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sizes,published", [((0, 0), True),
                                             ((2, 32), False),
                                             ((3, 0), False)])
def test_vit_configs_published_unless_sized(sizes, published):
    """``--mode vit`` builds the published ViT-Tiny + MoCo v3 heads with
    remat when neither ``--layers`` nor ``--d-model`` is given, else the
    reduced CPU variant exactly as before (remat off, 256/256/64 heads)."""
    from repro.launch.train import vit_configs
    cfg, ssl_cfg, tc = vit_configs(*sizes)
    if published:
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff) == \
            (12, 192, 3, 768)
        assert ssl_cfg == SSLConfig() and tc.remat
    else:
        layers, d = sizes[0] or 4, sizes[1] or 64
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff) == \
            (layers, d, 4, 2 * d)
        assert (ssl_cfg.proj_hidden, ssl_cfg.pred_hidden,
                ssl_cfg.proj_dim) == (256, 256, 64)
        assert not tc.remat and cfg.compute_dtype == "float32"
