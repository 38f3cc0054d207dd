"""Multi-client round execution engines for the FL driver.

Two interchangeable engines run the "train the sampled clients, then
aggregate" middle of a communication round (``repro.federated.driver`` owns
the stage schedule, LR, server calibration and comm accounting around them):

  sequential  the numerical reference — a Python loop over participants,
              each running ``client.local_train`` batch by batch.
  vmap        the vectorized engine — clients' shards are stacked on a
              leading axis (``data.partition.stack_shards``), the per-batch
              local step is ``jax.vmap``-ed over that axis and driven by a
              single ``lax.scan`` over local steps, and FedAvg
              (``aggregate.fedavg_stacked``) is fused into the same jit'd
              program: one XLA dispatch executes the whole round.

Parity: the vmap engine replays the sequential driver's exact per-client
RNG chain on the host (``client.replay_batch_plan``) and feeds the
resulting batch indices / per-step keys into the compiled program, so both
engines consume identical data in identical order; ragged shards are
padded to the longest client and padded steps are masked to a no-op.
See docs/engine.md.

Uploads route through the wire transport (``repro.federated.transport``)
in both engines: each client's result is packed into the round plan's
stage payload, encoded/decoded by the configured codec, and FedAvg
consumes the *decoded* trees reassembled onto the server's model. In the
vmap engine that whole path — pack, codec, error-feedback residual
update, FedAvg — is vmapped over clients inside the same jit'd round
program. With the identity (fp32) codec the round is bit-identical to
pre-transport behavior. See docs/transport.md.

The transport's host-called wire path (broadcast / upload decode) itself
has two engines, selected by ``Transport(kernels=...)`` /
``--transport-kernels``: the jit'd XLA reference and the fused Pallas
pack/codec kernels (docs/kernels.md). Both round engines pick that up
transparently — the sequential engine through ``aggregate_uploads``, the
vmap engine for its broadcasts; the vmap engine's *in-program* upload
path (``make_wire_transform``) stays XLA by design, since it is traced
into the jit'd round program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ssl as ssl_mod
from repro.data.partition import stack_shards
from repro.federated import aggregate, client as client_mod
from repro.federated import transport as transport_mod
from repro.obs import NOOP_OBS

ENGINES = ("sequential", "vmap")


def _pool_len(pool) -> int:
    return jax.tree.leaves(pool)[0].shape[0]


def _abstract_round_inputs(encoder, ssl_cfg, opt, images, batch_size):
    """Shape-only (eval_shape) state/opt/batch trees for AOT lowering —
    no parameters are materialized."""
    state = jax.eval_shape(
        lambda k: ssl_mod.ssl_init(k, encoder, ssl_cfg),
        jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, state["online"])
    img = jax.ShapeDtypeStruct((batch_size,) + tuple(images.shape[1:]),
                               images.dtype)
    return state, opt_state, img


def jit_cache_entries(fns) -> int:
    """Total compiled-specialization count across ``fns`` — jit'd
    callables expose ``_cache_size()``; plain host functions (the pallas
    wire path) count zero. The driver's jit-recompile counter diffs this
    against the previous round to surface silent retraces."""
    total = 0
    for f in fns:
        size = getattr(f, "_cache_size", None)
        if size is not None:
            total += size()
    return total


def step_prefix_reuse(ssl_cfg, plan) -> int:
    """``ssl.prefix_reuse`` of the local step built for ``plan``: target
    and alignment branch-view forwards a step that start from the shared
    frozen prefix."""
    return ssl_mod.prefix_reuse(ssl_cfg.method, plan.active_from,
                                plan.align and ssl_cfg.align_weight > 0.0)


def build_round_program(client_init, client_step, extract,
                        wire_transform=None, fedavg=True):
    """Compile a full FL round into one jit'd program.

    client_init(broadcast) -> carry          (per-client local state)
    client_step(carry, batch, key, lr, broadcast) -> (carry, loss)
    extract(carry) -> pytree to aggregate
    wire_transform(stacked_outs, broadcast, residuals)
        -> (decoded_stacked, new_residuals, clip_scales)
                                             (optional transport hook)
    fedavg=False skips the fused aggregation and returns the (decoded)
    client-stacked trees instead — the buffered-async round policy holds
    individual updates across rounds and averages them itself.

    The returned function has signature

        round(broadcast, shards, batch_idx, step_keys, valid, weights, lr)
          -> (aggregated_tree, (C,) last-step losses)

    or, when ``wire_transform`` is given, an extra trailing ``residuals``
    argument plus two extra results (new residuals and the (C,) DP clip
    scales): each client's extracted tree is packed onto the wire,
    DP-clipped when the transport carries a privacy engine,
    encoded/decoded by the transport codec (threading per-client
    error-feedback residuals through the program), and FedAvg consumes the
    *decoded* trees — the codec's quantization/sparsification error
    propagates into the aggregated model exactly as it would in a real
    deployment.

    ``broadcast`` is shared across clients (global state, alignment
    context), every leaf of ``shards`` is ``(C, n_max, ...)``, ``batch_idx``
    is ``(C, T, B)`` shard-local gather indices, ``step_keys`` is
    ``(C, T, 2)`` and ``valid`` is ``(C, T)``. Steps with ``valid=False``
    still execute (uniform trip count under vmap) but their state update is
    discarded, so padding never changes the result.
    """
    def run_clients(broadcast, shards, batch_idx, step_keys, valid, lr):
        def one_client(shard, idx, keys, ok):
            def body(carry, xs):
                c, last = carry
                i, k, v = xs
                batch = jax.tree.map(lambda a: a[i], shard)
                nc, loss = client_step(c, batch, k, lr, broadcast)
                keep = functools.partial(jnp.where, v)
                # XLA fuses the optimizer's elementwise update into this
                # select, and a fusion takes its root's scope
                with jax.named_scope("optimizer"):
                    nc = jax.tree.map(keep, nc, c)
                return (nc, jnp.where(v, loss, last)), None

            carry0 = (client_init(broadcast), jnp.float32(0.0))
            (c, last), _ = jax.lax.scan(body, carry0, (idx, keys, ok))
            return extract(c), last

        return jax.vmap(one_client)(shards, batch_idx, step_keys, valid)

    if wire_transform is None:
        def round_fn(broadcast, shards, batch_idx, step_keys, valid,
                     weights, lr):
            outs, losses = run_clients(broadcast, shards, batch_idx,
                                       step_keys, valid, lr)
            if not fedavg:
                return outs, losses
            with jax.named_scope("fedavg"):
                return aggregate.fedavg_stacked(outs, weights), losses
    else:
        def round_fn(broadcast, shards, batch_idx, step_keys, valid,
                     weights, lr, residuals):
            outs, losses = run_clients(broadcast, shards, batch_idx,
                                       step_keys, valid, lr)
            with jax.named_scope("wire"):
                decoded, new_res, scales = wire_transform(outs, broadcast,
                                                          residuals)
            if not fedavg:
                return decoded, losses, new_res, scales
            with jax.named_scope("fedavg"):
                avg = aggregate.fedavg_stacked(decoded, weights)
            return avg, losses, new_res, scales

    return jax.jit(round_fn)


class SequentialEngine:
    """Reference engine: the seed driver's per-client Python loop."""

    name = "sequential"

    def __init__(self, *, encoder, ssl_cfg, opt, fl, train_cfg, images,
                 client_indices, transport=None, obs=None):
        self.encoder, self.ssl_cfg, self.opt = encoder, ssl_cfg, opt
        self.fl, self.train_cfg = fl, train_cfg
        self.images, self.client_indices = images, client_indices
        self.counts = [len(ix) for ix in client_indices]
        self.transport = transport or transport_mod.Transport("fp32")
        self.obs = obs if obs is not None else NOOP_OBS
        self._steps: Dict[tuple, object] = {}

    def compile_cache_size(self) -> int:
        return jit_cache_entries(self._steps.values())

    def _step(self, plan):
        sig = (plan.sub_layers, plan.active_from, plan.align,
               plan.depth_dropout)
        if sig not in self._steps:
            self._steps[sig] = client_mod.make_local_step(
                self.encoder, self.ssl_cfg, self.opt,
                sub_layers=plan.sub_layers, active_from=plan.active_from,
                align=plan.align, depth_dropout=plan.depth_dropout)
        return self._steps[sig]

    def lower_round(self, plan, *, clients: int = 1):
        """AOT-lower this engine's compiled unit for ``plan`` with
        abstract inputs: the jit'd per-batch local step (``clients`` is
        accepted for signature parity with the vmap engine and ignored —
        the sequential unit is per-client by construction). The resource
        observatory reads ``cost_analysis``/``memory_analysis`` off the
        result; one program run = one local step over one batch, so
        per-sample FLOPs = flops / batch_size."""
        state, opt_state, img = _abstract_round_inputs(
            self.encoder, self.ssl_cfg, self.opt, self.images,
            self.train_cfg.batch_size)
        return self._step(plan).lower(
            state, opt_state, img,
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            state["online"]["enc"] if plan.align else None)

    def run_round(self, state, plan, participants, client_keys, lr,
                  global_enc, server_online, collect=False):
        tracer = self.obs.tracer
        step_fn = self._step(plan)
        reuse = step_prefix_reuse(self.ssl_cfg, plan)
        outs, losses = [], []
        for i, kc in zip(participants, client_keys):
            with tracer.span("client.train", cat="engine",
                             client=int(i), prefix_reuse=reuse) as sp:
                online_i, m = client_mod.local_train(
                    state, self.images[self.client_indices[i]], step_fn,
                    self.opt, epochs=self.fl.local_epochs,
                    batch_size=self.train_cfg.batch_size, key=kc, lr=lr,
                    global_enc=global_enc)
                outs.append(online_i)
                losses.append(float(m["loss"]))
                sp.set(loss=losses[-1])
        if collect:
            trees, stats = self.transport.decode_uploads(
                server_online, outs, participants, plan,
                ref_online=state["online"])
            return trees, losses, stats
        w = aggregate.client_weights([self.counts[i] for i in participants])
        with tracer.span("aggregate", cat="engine", engine=self.name,
                         clients=len(participants)):
            new_online, stats = self.transport.aggregate_uploads(
                server_online, outs, participants, plan, w,
                ref_online=state["online"])
        return new_online, losses, stats


class VmapEngine:
    """Vectorized engine: one compiled program per (plan signature)."""

    name = "vmap"

    def __init__(self, *, encoder, ssl_cfg, opt, fl, train_cfg, images,
                 client_indices, transport=None, obs=None):
        self.encoder, self.ssl_cfg, self.opt = encoder, ssl_cfg, opt
        self.fl, self.train_cfg = fl, train_cfg
        self.transport = transport or transport_mod.Transport("fp32")
        self.obs = obs if obs is not None else NOOP_OBS
        self.counts = [len(ix) for ix in client_indices]
        bs = train_cfg.batch_size
        if min(self.counts) < bs:
            # the sequential reference also cannot train such a client (it
            # would run zero local steps); fail loudly instead of silently
            # averaging an untrained client with a fabricated 0.0 loss
            raise ValueError(
                f"vmap engine needs every shard >= batch size: smallest "
                f"shard {min(self.counts)} < batch {bs}")
        self.total_steps = fl.local_epochs * max(c // bs
                                                 for c in self.counts)
        # stack padded shard *indices*, not data: per-round gathers pull
        # only the sampled participants' rows from the pool, so device
        # memory scales with clients_per_round x n_max, not N x n_max
        self._pool = images
        self._pad_idx, _ = stack_shards(
            jnp.arange(_pool_len(images)), client_indices)
        # full-participation rounds reuse the same shards/weights
        self._all = list(range(len(self.counts)))
        self._all_weights = aggregate.client_weights(self.counts)
        self._full_shards = None
        self._programs: Dict[tuple, object] = {}

    def compile_cache_size(self) -> int:
        return jit_cache_entries(self._programs.values())

    def _gather(self, idx):
        """(C, n_max) pool indices -> client-stacked shard data."""
        return jax.tree.map(lambda a: a[idx], self._pool)

    def _program(self, plan, spec, fedavg=True):
        sig = (plan.sub_layers, plan.active_from, plan.align,
               plan.depth_dropout, spec.sig, fedavg)
        if sig not in self._programs:
            step = client_mod.make_local_step(
                self.encoder, self.ssl_cfg, self.opt,
                sub_layers=plan.sub_layers, active_from=plan.active_from,
                align=plan.align, depth_dropout=plan.depth_dropout)
            opt = self.opt

            def client_init(bc):
                g = bc["state"]
                st = {"online": jax.tree.map(jnp.asarray, g["online"])}
                if "target" in g:
                    # target branch re-initialized from the downloaded
                    # global model, exactly like local_train
                    st["target"] = {
                        "enc": jax.tree.map(jnp.copy, g["online"]["enc"]),
                        "proj": jax.tree.map(jnp.copy, g["online"]["proj"]),
                    }
                return st, opt.init(st["online"])

            def client_step(carry, batch, key, lr, bc):
                st, os_ = carry
                st, os_, m = step(st, os_, batch, key, lr, bc["global_enc"])
                return (st, os_), m["loss"]

            wire = self.transport.make_wire_transform(spec)
            self._programs[sig] = build_round_program(
                client_init, client_step, lambda c: c[0]["online"],
                wire_transform=lambda outs, bc, res: wire(
                    outs, bc["server"], bc["state"]["online"], res),
                fedavg=fedavg)
        return self._programs[sig]

    def lower_round(self, plan, *, clients: int = 1):
        """AOT-lower the full jit'd round program for ``plan`` with
        abstract inputs: ``clients`` stacked participants at scan trip
        count 1 (XLA's cost analysis counts a rolled loop body once, so
        trip count 1 makes the count exact — one local step per client,
        plus the in-program wire path and FedAvg). Per-sample FLOPs =
        flops / (clients * batch_size)."""
        state, opt_state, img = _abstract_round_inputs(
            self.encoder, self.ssl_cfg, self.opt, self._pool,
            self.train_cfg.batch_size)
        spec = self.transport.plan_specs(state["online"], plan)["upload"]
        C, T, B = clients, 1, self.train_cfg.batch_size
        n_max = self._pad_idx.shape[1]
        residuals = jax.eval_shape(
            lambda: self.transport.gather_residuals(list(range(C)), spec))
        broadcast = {"state": state,
                     "global_enc": (state["online"]["enc"]
                                    if plan.align else None),
                     "server": state["online"]}
        shards = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((C, n_max) + tuple(a.shape[1:]),
                                           a.dtype), self._pool)
        return self._program(plan, spec).lower(
            broadcast, shards,
            jax.ShapeDtypeStruct((C, T, B), jnp.int32),
            jax.ShapeDtypeStruct((C, T, 2), jnp.uint32),
            jax.ShapeDtypeStruct((C, T), jnp.bool_),
            jax.ShapeDtypeStruct((C,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            residuals)

    def run_round(self, state, plan, participants, client_keys, lr,
                  global_enc, server_online, collect=False):
        bs = self.train_cfg.batch_size
        tracer = self.obs.tracer
        # host work the round program waits for: the batch plans, the
        # shard gather and the payload layout
        with tracer.span("engine.plan", cat="engine"):
            idxs, keys, valids = [], [], []
            for i, kc in zip(participants, client_keys):
                bi, sk, v = client_mod.replay_batch_plan(
                    kc, self.counts[i], self.fl.local_epochs, bs,
                    self.total_steps)
                idxs.append(bi)
                keys.append(sk)
                valids.append(v)
            if list(participants) == self._all:
                if self._full_shards is None:
                    self._full_shards = self._gather(self._pad_idx)
                shards, w = self._full_shards, self._all_weights
            else:
                pidx = jnp.asarray(np.asarray(participants, np.int32))
                shards = self._gather(self._pad_idx[pidx])
                w = aggregate.client_weights(
                    [self.counts[i] for i in participants])
            spec = self.transport.plan_specs(server_online, plan)["upload"]
            residuals = self.transport.gather_residuals(participants, spec)
        # the whole round — every client's local steps, the in-program
        # wire path and FedAvg — is one dispatch; this span closes once
        # the program is enqueued, and the wait for it is engine.readback
        with tracer.span("engine.dispatch", cat="engine",
                         engine=self.name, participants=len(participants),
                         programs=len(self._programs),
                         prefix_reuse=step_prefix_reuse(self.ssl_cfg,
                                                        plan)):
            result, losses, new_res, scales = self._program(
                plan, spec, fedavg=not collect)(
                {"state": state, "global_enc": global_enc,
                 "server": server_online}, shards,
                jnp.stack(idxs), jnp.stack(keys),
                jnp.asarray(np.stack(valids)), w, jnp.float32(lr),
                residuals)
        self.transport.store_residuals(participants, spec, new_res)
        if collect:
            # unstack the decoded client axis into per-client trees (the
            # async policy holds them individually across rounds)
            result = [jax.tree.map(lambda a, i=i: a[i], result)
                      for i in range(len(participants))]
        stats = dict(self.transport.upload_stats(spec))
        # the host waits here for the round program to finish
        with tracer.span("engine.readback", cat="engine"):
            stats["clip_fraction"] = float(
                np.mean(np.asarray(scales, np.float32) < 1.0))
            losses = [float(x) for x in np.asarray(losses)]
        return result, losses, stats


def make_engine(name: str, **kw):
    if name == "sequential":
        return SequentialEngine(**kw)
    if name == "vmap":
        return VmapEngine(**kw)
    raise ValueError(f"unknown engine '{name}'; one of {ENGINES}")
