"""Server-side mechanisms: calibration (paper Algorithm 1 line 7) and the
round bookkeeping (stage transitions, weight transfer, client sampling).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.data.augment import two_views
from repro.federated.masks import stage_update_mask


def make_calibration_step(encoder, ssl_cfg, opt, *, sub_layers: int):
    """End-to-end SSL step over the current sub-model (active_from=0)."""
    @jax.jit
    def step(state, opt_state, images, key, lr):
        with jax.named_scope("calibrate"):
            with jax.named_scope("augment"):
                x1, x2 = two_views(key, images)

            def loss_fn(online):
                st = {**state, "online": online}
                return ssl_mod.ssl_loss(st, x1, x2, encoder, ssl_cfg,
                                        sub_layers=sub_layers, active_from=0)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["online"])
            with jax.named_scope("optimizer"):
                mask = stage_update_mask(state["online"], sub_layers, 0)
                new_online, opt_state = opt.update(grads, opt_state,
                                                   state["online"], lr, mask)
                state = {**state, "online": new_online}
                state = ssl_mod.momentum_update(state, ssl_cfg.momentum)
        return state, opt_state, metrics

    return step


def server_calibrate(state, aux_images, step_fn, opt, *, epochs: int,
                     batch_size: int, key, lr):
    """Train the aggregated sub-model end-to-end on D_g (Algorithm 1 l.7).

    Uses the server's own optimizer state (fresh per round, like clients).
    """
    opt_state = opt.init(state["online"])
    n = aux_images.shape[0]
    bs = min(batch_size, n)
    for e in range(epochs):
        key, kp = jax.random.split(key)
        perm = jax.random.permutation(kp, n)
        for b in range(n // bs):
            key, kb = jax.random.split(key)
            sel = jax.lax.dynamic_slice_in_dim(perm, b * bs, bs)
            state, opt_state, _ = step_fn(state, opt_state,
                                          aux_images[sel], kb, lr)
    return state


def broadcast_download(state, plan, transport):
    """Server -> clients (paper Fig. 1 step i): push the round plan's
    download payload over the wire transport and return the state clients
    actually train from, plus measured wire stats.

    With the identity codec the returned tree is bit-identical to
    ``state``; with a lossy codec the decoded download is what every client
    (and the alignment loss's global model) sees, so wire compression error
    reaches local training exactly as it would in a real deployment. Leaves
    outside the payload keep the server values — they stand in for the
    client's cached copy from earlier rounds, which the plan says is still
    current.
    """
    view, stats = transport.broadcast(state["online"], plan)
    return {**state, "online": view}, stats


def begin_stage(state, stage: int, *, weight_transfer: bool):
    """Stage-transition housekeeping: L_{s-1} -> L_s weight transfer."""
    if not weight_transfer or stage < 2:
        return state
    online = dict(state["online"])
    online["enc"] = sched.transfer_model(online["enc"], None, stage)
    out = {**state, "online": online}
    if "target" in state:
        out["target"] = {
            "enc": sched.transfer_model(dict(state["target"]["enc"]), None,
                                        stage),
            "proj": state["target"]["proj"],
        }
    return out


def sample_clients(key, num_clients: int, clients_per_round: int, *,
                   overcommit: float = 1.0):
    """Sample the round's cohort. ``overcommit > 1`` (the deadline
    policy's straggler insurance) inflates the sample by that factor,
    clamped to the population; ``overcommit=1`` is byte-for-byte the
    historical behavior (same key, same draw)."""
    n = clients_per_round or num_clients
    n = min(num_clients, math.ceil(n * overcommit))
    if n >= num_clients:
        return list(range(num_clients))
    idx = jax.random.choice(key, num_clients, (n,), replace=False)
    return [int(i) for i in idx]
