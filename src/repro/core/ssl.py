"""Self-supervised learning engines: MoCo v3 (paper default), SimCLR, BYOL.

State layout (a pytree, usable directly under pjit):

    {"online": {"enc": F, "proj": H, "pred": P},
     "target": {"enc": F_k, "proj": H_k}}

The encoder is abstracted behind an ``Encoder`` record so the same SSL code
drives the paper's ViT-Tiny on images and the assigned LM architectures on
token sequences (representation = mean-pooled final hidden state).

MoCo v3 local loss with representation alignment is Algorithm 2 of the
paper; ``momentum_update`` is the target-branch EMA; the server-side
calibration step (Algorithm 1, line 7) reuses ``ssl_loss`` with
``active_from=0`` — end-to-end over the current sub-model.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import heads, losses
from repro.models import lm as lm_mod
from repro.models import vit as vit_mod


# ---------------------------------------------------------------------------
# Encoder abstraction
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Encoder:
    init: Callable[..., Any]            # (key) -> params
    apply: Callable[..., Any]           # (params, x, sub_layers, active_from,
    #                                      layer_gates) -> (B, d_repr)
    d_repr: int
    num_stages: int


def make_vit_encoder(cfg, image_size: int = 32, patch_size: int = 4,
                     remat: bool = False) -> Encoder:
    """``remat`` checkpoints each block (``TrainConfig.remat``): the
    backward pass recomputes block activations instead of keeping them."""
    def init(key):
        return vit_mod.init_vit(key, cfg, image_size, patch_size)

    def apply(params, x, sub_layers=None, active_from=0, layer_gates=None):
        return vit_mod.vit_forward(params, x, cfg, patch_size=patch_size,
                                   sub_layers=sub_layers,
                                   active_from=active_from, remat=remat,
                                   layer_gates=layer_gates)

    return Encoder(init, apply, cfg.d_model, cfg.num_layers)


def make_lm_encoder(cfg) -> Encoder:
    """Token encoder: mean-pooled final hidden state of the (sub-)model."""
    def init(key):
        return lm_mod.init_lm(key, cfg)

    def apply(params, tokens, sub_layers=None, active_from=0, layer_gates=None):
        x = lm_mod.embed(params, tokens, cfg)
        h, _ = lm_mod.forward_hidden(params, x, cfg, sub_layers=sub_layers,
                                     active_from=active_from)
        return jnp.mean(h.astype(jnp.float32), axis=1)

    return Encoder(init, apply, cfg.d_model, lm_mod.num_stages(cfg))


# ---------------------------------------------------------------------------
# init / EMA
# ---------------------------------------------------------------------------
def ssl_init(key, encoder: Encoder, ssl_cfg, dtype=jnp.float32):
    ke, kp, kq = jax.random.split(key, 3)
    enc = encoder.init(ke)
    proj = heads.proj_init(kp, encoder.d_repr, ssl_cfg.proj_hidden,
                           ssl_cfg.proj_dim, dtype)
    online = {"enc": enc, "proj": proj}
    if ssl_cfg.method in ("moco_v3", "byol"):
        online["pred"] = heads.pred_init(kq, ssl_cfg.proj_dim,
                                         ssl_cfg.pred_hidden,
                                         ssl_cfg.proj_dim, dtype)
    state = {"online": online}
    if ssl_cfg.method in ("moco_v3", "byol"):
        state["target"] = {"enc": jax.tree.map(jnp.copy, enc),
                           "proj": jax.tree.map(jnp.copy, proj)}
    return state


def momentum_update(state, mu: float):
    """target <- mu * target + (1 - mu) * online  (Algorithm 2, line 15)."""
    if "target" not in state:
        return state
    new_t = jax.tree.map(
        lambda t, o: mu * t + (1.0 - mu) * o.astype(t.dtype),
        state["target"],
        {"enc": state["online"]["enc"], "proj": state["online"]["proj"]})
    return {**state, "target": new_t}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def _branch(enc_params, head_params, pred_params, x, encoder: Encoder,
            sub_layers, active_from, layer_gates=None):
    z = encoder.apply(enc_params, x, sub_layers, active_from, layer_gates)
    with jax.named_scope("heads"):
        p = heads.head_apply(head_params, z)
        if pred_params is not None:
            p = heads.head_apply(pred_params, p)
    return z, p


def ssl_loss(state, x1, x2, encoder: Encoder, ssl_cfg, *,
             sub_layers: Optional[int] = None, active_from: int = 0,
             layer_gates=None, global_enc=None, align_weight: float = 0.0):
    """Local SSL loss for a pair of augmented views (Algorithm 2, lines 6-13).

    Returns (loss, metrics). ``global_enc`` (the broadcast global encoder) is
    only needed when ``align_weight > 0`` — representation alignment, Eq. 3.
    """
    o = state["online"]
    method = ssl_cfg.method
    tau = ssl_cfg.temperature
    if method not in ("moco_v3", "simclr", "byol"):
        raise ValueError(method)

    # the scopes name each branch in the HLO's op_name metadata, so a
    # profiler trace splits the step's device time by branch
    pred = None if method == "simclr" else o["pred"]
    with jax.named_scope("online"):
        z1, q1 = _branch(o["enc"], o["proj"], pred, x1, encoder,
                         sub_layers, active_from, layer_gates)
        z2, q2 = _branch(o["enc"], o["proj"], pred, x2, encoder,
                         sub_layers, active_from, layer_gates)
    if method != "simclr":
        t = state["target"]
        with jax.named_scope("target"):
            _, k1 = _branch(t["enc"], t["proj"], None, x1, encoder,
                            sub_layers, sub_layers or encoder.num_stages)
            _, k2 = _branch(t["enc"], t["proj"], None, x2, encoder,
                            sub_layers, sub_layers or encoder.num_stages)
    with jax.named_scope("loss"):
        if method == "moco_v3":
            loss = losses.moco_contrastive(q1, k2, q2, k1, tau)
        elif method == "simclr":
            loss = losses.simclr_nt_xent(q1, q2, tau)
        else:
            loss = (losses.byol_regression(q1, k2)
                    + losses.byol_regression(q2, k1))

    metrics = {"con": loss}
    if align_weight > 0.0:
        assert global_enc is not None, "alignment needs the global encoder"
        with jax.named_scope("align"):
            zg1 = encoder.apply(global_enc, x1, sub_layers, 0)
            zg2 = encoder.apply(global_enc, x2, sub_layers, 0)
        with jax.named_scope("loss"):
            la = losses.align_loss(z1, zg2, z2, zg1, tau)
            loss = loss + align_weight * la
        metrics["align"] = la
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# LM-family SSL: next-token prediction + representation alignment
# ---------------------------------------------------------------------------
def lm_ssl_loss(params, batch, cfg, *, sub_layers=None, active_from: int = 0,
                global_params=None, align_weight: float = 0.0,
                tau: float = 0.2, remat: bool = False):
    """Self-supervised loss for assigned LM architectures.

    Next-token cross-entropy (the LM-native SSL objective) over the stage-s
    sub-model, plus the paper's Eq. 3 alignment between local and global
    mean-pooled hidden states when ``align_weight > 0``.
    """
    x = lm_mod.embed(params, batch["tokens"], cfg, batch.get("frontend"))
    hidden, aux = lm_mod.forward_hidden(params, x, cfg, sub_layers=sub_layers,
                                        active_from=active_from, remat=remat)
    P = 0 if batch.get("frontend") is None else batch["frontend"].shape[1]
    h_tok = hidden[:, P:] if P else hidden
    xent = lm_mod.xent_loss(params, h_tok, batch["labels"], cfg,
                            batch.get("mask"))
    loss = xent + aux
    metrics = {"xent": xent, "aux": aux}
    if align_weight > 0.0 and global_params is not None:
        z_local = jnp.mean(hidden.astype(jnp.float32), axis=1)
        xg = lm_mod.embed(global_params, batch["tokens"], cfg,
                          batch.get("frontend"))
        hg, _ = lm_mod.forward_hidden(global_params, xg, cfg,
                                      sub_layers=sub_layers, active_from=0)
        z_global = jax.lax.stop_gradient(
            jnp.mean(hg.astype(jnp.float32), axis=1))
        la = losses.info_nce(z_local, z_global, tau)
        loss = loss + align_weight * la
        metrics["align"] = la
    metrics["loss"] = loss
    return loss, metrics
