"""Self-supervised learning engines: MoCo v3 (paper default), SimCLR, BYOL.

State layout (a pytree, usable directly under pjit):

    {"online": {"enc": F, "proj": H, "pred": P},
     "target": {"enc": F_k, "proj": H_k}}

The encoder is abstracted behind an ``Encoder`` record whose forward comes
in two parts: the frozen ``prefix`` and the ``suffix`` over the blocks the
stage trains. At a layer-wise stage ``ssl_loss`` computes the prefix once
per view and starts the online, target and alignment branches from it.

MoCo v3 local loss with representation alignment is Algorithm 2 of the
paper; ``momentum_update`` is the target-branch EMA; the server-side
calibration step (Algorithm 1, line 7) reuses ``ssl_loss`` with
``active_from=0`` — end-to-end over the current sub-model.
"""
from __future__ import annotations

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
    prefix: Callable[..., Any]          # (params, x, active_from,
    #                                      layer_gates) -> activations
    suffix: Callable[..., Any]          # (params, h, sub_layers, active_from,
    #                                      layer_gates) -> (B, d_repr)
    d_repr: int
    num_stages: int

    def apply(self, params, x, sub_layers=None, active_from=0,
              layer_gates=None):
        """The whole forward, ``suffix`` over ``prefix``."""
        sub = self.num_stages if sub_layers is None else sub_layers
        act = max(0, min(active_from, sub))
        h = self.prefix(params, x, act, layer_gates)
        return self.suffix(params, h, sub, act, layer_gates)


def make_vit_encoder(cfg, image_size: int = 32, patch_size: int = 4,
                     remat: bool = False) -> Encoder:
    """``remat`` checkpoints each block (``TrainConfig.remat``): the
    backward pass recomputes block activations instead of keeping them."""
    def init(key):
        return vit_mod.init_vit(key, cfg, image_size, patch_size)

    def prefix(params, x, active_from=0, layer_gates=None):
        return vit_mod.vit_prefix(params, x, cfg, patch_size=patch_size,
                                  active_from=active_from, remat=remat,
                                  layer_gates=layer_gates)

    def suffix(params, h, sub_layers=None, active_from=0, layer_gates=None):
        return vit_mod.vit_suffix(params, h, cfg, sub_layers=sub_layers,
                                  active_from=active_from, remat=remat,
                                  layer_gates=layer_gates)

    return Encoder(init, prefix, suffix, cfg.d_model, cfg.num_layers)


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
def prefix_reuse(method: str, active_from: int, align: bool) -> int:
    """Target and alignment branch-view forwards per step that start from
    the shared frozen prefix instead of the patch embedding.

    Within a round the three branches' prefixes ``[0, active_from)`` hold
    the same weights: the target is re-copied from the broadcast, the
    update mask keeps the online prefix as broadcast, and the alignment's
    global encoder is the broadcast. The target's EMA of equal values
    differs from them by rounding only."""
    if active_from <= 0:
        return 0
    return 2 * (int(method != "simclr") + int(align))


def _heads(z, head_params, pred_params):
    with jax.named_scope("heads"):
        p = heads.head_apply(head_params, z)
        if pred_params is not None:
            p = heads.head_apply(pred_params, p)
    return p


def ssl_loss(state, x1, x2, encoder: Encoder, ssl_cfg, *,
             sub_layers: Optional[int] = None, active_from: int = 0,
             layer_gates=None, global_enc=None, align_weight: float = 0.0):
    """Local SSL loss for a pair of augmented views (Algorithm 2, lines 6-13).

    Returns (loss, metrics). ``global_enc`` (the broadcast global encoder) is
    only needed when ``align_weight > 0`` — representation alignment, Eq. 3.

    At a layer-wise stage (``active_from > 0``) the frozen prefix is
    computed once per view from the online weights, ungated, and every
    branch whose prefix sees the same gates starts its suffix from it
    (``prefix_reuse``); an online branch under depth-dropout gates runs
    its own gated prefix.
    """
    o = state["online"]
    method = ssl_cfg.method
    tau = ssl_cfg.temperature
    if method not in ("moco_v3", "simclr", "byol"):
        raise ValueError(method)
    sub = sub_layers or encoder.num_stages
    act = max(0, min(active_from, sub))
    views = (x1, x2)

    # the scopes name each branch in the HLO's op_name metadata, so a
    # profiler trace splits the step's device time by branch
    shared = None
    if prefix_reuse(method, act, align_weight > 0.0):
        with jax.named_scope("online"):
            shared = [encoder.prefix(o["enc"], x, act) for x in views]

    prev = None     # the output of the last suffix run

    def rep(enc_params, i, from_layer, gates=None):
        """View i's representation, from the shared prefix when its gates
        are the shared prefix's (none)."""
        nonlocal prev
        if shared is None or gates is not None:
            return encoder.apply(enc_params, views[i], sub, from_layer,
                                 gates)
        h = shared[i]
        if prev is not None:
            # one suffix after another: left free, XLA runs the branches'
            # suffixes side by side and holds all their temporaries at
            # once (0.8 GB more at ViT-Tiny, 8 clients, batch 1024)
            h = jax.lax.optimization_barrier(
                (h, jax.lax.stop_gradient(prev)))[0]
        prev = encoder.suffix(enc_params, h, sub, act)
        return prev

    pred = None if method == "simclr" else o["pred"]
    with jax.named_scope("online"):
        z1 = rep(o["enc"], 0, active_from, layer_gates)
        q1 = _heads(z1, o["proj"], pred)
        z2 = rep(o["enc"], 1, active_from, layer_gates)
        q2 = _heads(z2, o["proj"], pred)
    if method != "simclr":
        t = state["target"]
        with jax.named_scope("target"):
            # the target is never differentiated
            k1 = _heads(jax.lax.stop_gradient(rep(t["enc"], 0, sub)),
                        t["proj"], None)
            k2 = _heads(jax.lax.stop_gradient(rep(t["enc"], 1, sub)),
                        t["proj"], None)
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
            zg1 = rep(global_enc, 0, 0)
            zg2 = rep(global_enc, 1, 0)
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
