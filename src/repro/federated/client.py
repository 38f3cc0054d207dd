"""Client-side local SSL training (paper Algorithm 2).

``make_local_step`` builds the jit'd per-batch train step for a given
(stage, schedule) configuration; ``local_train`` runs E local epochs.
The online branch, target branch and optimizer state are all local to the
client for the duration of the round; the target branch is re-initialized
from the downloaded global model at round start (Algorithm 2, lines 2-3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.data.augment import two_views
from repro.federated.masks import stage_update_mask


def make_local_step(encoder, ssl_cfg, opt, *, sub_layers: int,
                    active_from: int, align: bool, depth_dropout: float):
    """Returns jit'd step(state, opt_state, images, key, lr, global_enc)."""
    align_w = ssl_cfg.align_weight if align else 0.0

    @jax.jit
    def step(state, opt_state, images, key, lr, global_enc):
        k_aug, k_dd = jax.random.split(key)
        with jax.named_scope("augment"):
            x1, x2 = two_views(k_aug, images)
        gates = None
        if depth_dropout > 0.0:
            gates = sched.depth_dropout_gates(
                k_dd, encoder.num_stages, active_from, depth_dropout)

        def loss_fn(online):
            st = {**state, "online": online}
            return ssl_mod.ssl_loss(
                st, x1, x2, encoder, ssl_cfg, sub_layers=sub_layers,
                active_from=active_from, layer_gates=gates,
                global_enc=global_enc, align_weight=align_w)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["online"])
        with jax.named_scope("optimizer"):
            mask = stage_update_mask(state["online"], sub_layers,
                                     active_from)
            new_online, opt_state = opt.update(grads, opt_state,
                                               state["online"], lr, mask)
            state = {**state, "online": new_online}
            state = ssl_mod.momentum_update(state, ssl_cfg.momentum)
        return state, opt_state, metrics

    return step


def local_train(global_state, images, step_fn, opt, *, epochs: int,
                batch_size: int, key, lr, global_enc=None):
    """Run E local epochs (Algorithm 2). Returns (online_params, metrics).

    ``images``: (n_i, H, W, 3) this client's local shard.
    """
    state = {
        "online": jax.tree.map(jnp.asarray, global_state["online"]),
    }
    if "target" in global_state:
        # target branch re-initialized from the global model each round
        state["target"] = {
            "enc": jax.tree.map(jnp.copy, global_state["online"]["enc"]),
            "proj": jax.tree.map(jnp.copy, global_state["online"]["proj"]),
        }
    opt_state = opt.init(state["online"])
    n = images.shape[0]
    steps = 0
    last = {}
    for e in range(epochs):
        key, kp = jax.random.split(key)
        perm = jax.random.permutation(kp, n)
        nb = n // batch_size
        for b in range(nb):
            key, kb = jax.random.split(key)
            sel = jax.lax.dynamic_slice_in_dim(perm, b * batch_size,
                                               batch_size)
            batch = images[sel]
            state, opt_state, last = step_fn(state, opt_state, batch, kb,
                                             lr, global_enc)
            steps += 1
    return state["online"], {**last, "steps": steps}


def replay_batch_plan(key, n: int, epochs: int, batch_size: int,
                      total_steps: int):
    """Host-side replay of ``local_train``'s RNG/batch chain for one client.

    Performs exactly the key splits and permutations ``local_train`` would,
    so the vectorized engine (``repro.federated.engine``) consumes identical
    batches and per-step keys and matches the sequential reference. Returns

        batch_idx  (total_steps, batch_size) int32 — shard-local positions
        step_keys  (total_steps, 2) uint32         — per-step PRNG keys
        valid      (total_steps,) bool             — False for padded steps

    Clients with fewer than ``total_steps`` real steps (ragged shards) are
    padded at the end; padded steps carry index 0 / key 0 and must be
    masked out by the caller.
    """
    nb = n // batch_size
    if epochs * nb > total_steps:
        raise ValueError(f"client needs {epochs * nb} steps > padded "
                         f"budget {total_steps}")
    batch_idx, step_keys = _replay_plan_jit(
        key, n=n, epochs=epochs, batch_size=batch_size,
        total_steps=total_steps)
    valid = np.zeros((total_steps,), bool)
    valid[:epochs * nb] = True
    return batch_idx, step_keys, valid


@functools.partial(jax.jit,
                   static_argnames=("n", "epochs", "batch_size",
                                    "total_steps"))
def _replay_plan_jit(key, *, n, epochs, batch_size, total_steps):
    """The split/permute chain of ``local_train``, unrolled in one program
    so the vmap engine pays one dispatch per client instead of one per
    split."""
    nb = n // batch_size
    batch_idx = jnp.zeros((total_steps, batch_size), jnp.int32)
    step_keys = jnp.zeros((total_steps, 2), jnp.uint32)
    t = 0
    for _ in range(epochs):
        key, kp = jax.random.split(key)
        perm = jax.random.permutation(kp, n).astype(jnp.int32)
        for b in range(nb):
            key, kb = jax.random.split(key)
            batch_idx = batch_idx.at[t].set(
                perm[b * batch_size:(b + 1) * batch_size])
            step_keys = step_keys.at[t].set(kb)
            t += 1
    return batch_idx, step_keys
