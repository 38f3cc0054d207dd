"""Core SSL machinery: losses, heads, MoCo v3 engine, momentum EMA."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SSLConfig
from repro.core import heads, losses, schedule as sched, ssl as ssl_mod

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


# ---------------------------------------------------------------------------
# the shared frozen prefix against each branch running its own forward
# ---------------------------------------------------------------------------
VIT3 = dataclasses.replace(VIT, num_layers=3)
MOCO = dataclasses.replace(SSLC, align_weight=0.5)


def _with_prefix(enc, src, act):
    """``enc`` with the patch embedding and blocks ``[0, act)`` of
    ``src``: a branch whose frozen prefix is the online encoder's."""
    out = {**enc, "patch": src["patch"], "pos": src["pos"],
           "cls": src["cls"]}
    out["blocks"] = jax.tree.map(lambda e, s: e.at[:act].set(s[:act]),
                                 enc["blocks"], src["blocks"])
    return out


def _plain_loss(state, x1, x2, encoder, sc, act, gates, g_enc, align_w):
    """MoCo v3 with alignment, each branch view a whole ``apply`` from the
    patch embedding: the composition the shared prefix must match."""
    o, t, sub = state["online"], state["target"], encoder.num_stages

    def q(x):
        z = encoder.apply(o["enc"], x, sub, act, gates)
        return z, heads.head_apply(o["pred"], heads.head_apply(o["proj"], z))

    def k(x):
        return heads.head_apply(t["proj"],
                                encoder.apply(t["enc"], x, sub, sub))

    (z1, q1), (z2, q2) = q(x1), q(x2)
    loss = losses.moco_contrastive(q1, k(x2), q2, k(x1), sc.temperature)
    metrics = {"con": loss}
    if align_w > 0.0:
        zg1 = encoder.apply(g_enc, x1, sub, 0)
        zg2 = encoder.apply(g_enc, x2, sub, 0)
        la = losses.align_loss(z1, zg2, z2, zg1, sc.temperature)
        loss = loss + align_w * la
        metrics["align"] = la
    metrics["loss"] = loss
    return loss, metrics


def _shared_and_plain(act, gated, align, ema_steps=0):
    """(loss, metrics, online grads) of ``ssl_loss`` and of the plain
    composition, on a 3-block encoder whose target and global encoders
    differ from the online one past the prefix ``[0, act)``."""
    encoder = ssl_mod.make_vit_encoder(VIT3)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(7), 3)
    state = ssl_mod.ssl_init(k0, encoder, MOCO)
    o = state["online"]
    other = ssl_mod.ssl_init(k1, encoder, MOCO)["online"]
    t_enc = _with_prefix(other["enc"], o["enc"], act)
    for _ in range(ema_steps):          # the EMA of an unchanged prefix
        t_enc = jax.tree.map(
            lambda t, x: MOCO.momentum * t + (1.0 - MOCO.momentum) * x,
            t_enc, _with_prefix(t_enc, o["enc"], act))
    state["target"] = {"enc": t_enc, "proj": other["proj"]}
    g_enc = _with_prefix(
        jax.tree.map(lambda a: 1.1 * a, other["enc"]), o["enc"], act)
    # depth-dropout gates: frozen block 0 dropped, active blocks kept
    gates = (jnp.where(jnp.arange(3) >= act, 1.0,
                       jnp.asarray([0.0, 1.0, 0.0])) if gated else None)
    x1, x2 = jax.random.normal(k2, (2, 8, 32, 32, 3))
    align_w = MOCO.align_weight if align else 0.0

    def shared(online):
        return ssl_mod.ssl_loss(
            {**state, "online": online}, x1, x2, encoder, MOCO,
            sub_layers=3, active_from=act, layer_gates=gates,
            global_enc=g_enc, align_weight=align_w)

    def plain(online):
        return _plain_loss({**state, "online": online}, x1, x2, encoder,
                           MOCO, act, gates, g_enc, align_w)

    # op by op, so the comparison sees the algorithm and not how XLA fuses
    # two different programs (compiled on the CPU, the two forms' online
    # gradients differ by about 2e-6 relative)
    with jax.disable_jit():
        return [jax.value_and_grad(f, has_aux=True)(o)
                for f in (shared, plain)]


def _rel(a, b):
    """Relative distance of two arrays or trees, each taken as one
    vector."""
    a, b = (np.concatenate([np.ravel(np.asarray(x, np.float64))
                            for x in jax.tree.leaves(t)]) for t in (a, b))
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "dd"])
@pytest.mark.parametrize("act", [0, 1, 2])
@pytest.mark.parametrize("align", [False, True], ids=["noalign", "align"])
def test_shared_prefix_matches_unshared(align, act, gated):
    """MoCo v3 with the frozen prefix computed once per view gives the
    loss, metrics and online gradients of every branch running its own
    forward: bit for bit at ``active_from == 0`` (nothing is shared),
    within 1e-6 relative otherwise (float32 on the CPU)."""
    ((ls, ms), gs), ((lp, mp), gp) = _shared_and_plain(act, gated, align)
    assert set(ms) == set(mp) == ({"con", "align", "loss"} if align
                                  else {"con", "loss"})
    assert float(ls) == float(ms["loss"])
    if act == 0:
        for a, b in [(ms[k], mp[k]) for k in ms] + list(
                zip(jax.tree.leaves(gs), jax.tree.leaves(gp))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        for k in ms:
            assert _rel(ms[k], mp[k]) <= 1e-6, (k, _rel(ms[k], mp[k]))
        assert _rel(gs, gp) <= 1e-6, _rel(gs, gp)


def test_shared_prefix_within_ema_rounding():
    """The target's prefix is the EMA of an unchanged online prefix, equal
    to it in exact arithmetic; after 36 steps (a round of 3 epochs of 12
    steps) it differs by float32 rounding, and sharing the online prefix
    instead moves the loss and metrics by about 2e-7 relative and the
    online gradients by about 2e-6: under 1e-5."""
    for act in (1, 2):
        ((ls, ms), gs), ((lp, mp), gp) = _shared_and_plain(
            act, False, True, ema_steps=36)
        for k in ms:
            assert _rel(ms[k], mp[k]) <= 1e-5, (k, _rel(ms[k], mp[k]))
        assert _rel(gs, gp) <= 1e-5, _rel(gs, gp)


@pytest.mark.parametrize("schedule,stage,reuse", [
    ("lw_fedssl", 2, 4), ("lw_fedssl", 12, 4), ("lw_fedssl", 1, 0),
    ("layerwise", 6, 2), ("fll_dd", 6, 2), ("e2e", 12, 0),
    ("progressive", 6, 0)])
def test_prefix_reuse_by_schedule(schedule, stage, reuse):
    """Target and alignment branch-view forwards a local step starts from
    the shared prefix: both branches at an LW-FedSSL stage past the
    first, the target alone under FedMoCo-LW and FLL+DD (whose online
    branch runs its own gated prefix), none where nothing is frozen."""
    from repro.configs.base import FLConfig
    from repro.federated.engine import step_prefix_reuse
    fl = FLConfig(rounds=12, schedule=schedule)
    plan = next(p for p in sched.build_schedule(fl, 12) if p.stage == stage)
    assert step_prefix_reuse(SSLConfig(), plan) == reuse
    # server calibration trains end to end: nothing is frozen
    assert ssl_mod.prefix_reuse("moco_v3", 0, False) == 0
