"""Federated SSL training launcher.

Two modes:
  vit   — the paper's experiment: ViT backbone + MoCo v3 federated SSL on
          synthetic images (STL-10 stand-in), any of the five schedules.
  lm    — LM-family FedSSL: clients run next-token SSL + representation
          alignment on synthetic token shards (reduced arch on CPU).

On the production mesh the per-client local step is the pjit'd program the
dry-run lowers (repro.launch.steps); this launcher exercises the identical
round/stage logic at host scale so the whole FL system is runnable
end-to-end in this container.

Either mode runs on one of two round engines (``--engine``): ``sequential``
trains sampled clients one at a time (the numerical reference), ``vmap``
stacks them on a leading axis and executes each round — all clients' local
steps plus FedAvg — as a single jit'd program (``repro.federated.engine``).

Both modes route every download/upload through the wire transport
(``--codec``: fp32 | fp16 | bf16 | int8 | topk[:frac]), on either wire
engine (``--transport-kernels``: xla | pallas — the latter is the fused
pack/codec kernel path, docs/kernels.md); see docs/transport.md for
payload layout and codec semantics.

Privacy (both modes): ``--dp-clip / --dp-noise-multiplier / --dp-delta /
--dp-epsilon-budget`` enable client-level DP-FedAvg with RDP accounting,
``--secure-agg`` swaps FedAvg for pairwise-mask fixed-point secure
aggregation; see docs/privacy.md.

Example:
  PYTHONPATH=src python -m repro.launch.train --mode vit \
      --schedule lw_fedssl --rounds 12 --clients 4 --batch 64 \
      --engine vmap --codec int8
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (FLConfig, SSLConfig, TrainConfig, load_arch,
                                load_train, reduced)
from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.data import iid_partition, dirichlet_partition, synthetic_images
from repro.data.synthetic import synthetic_tokens
from repro.federated import aggregate, comm
from repro.federated import fleet as fleet_mod
from repro.federated import simulation as sim_mod
from repro.federated import transport as transport_mod
from repro.federated.driver import run_fedssl
from repro.launch.compile_cache import enable_compile_cache
from repro.federated import eval as fl_eval
from repro.obs import (ConsoleRenderer, format_round_line, make_obs,
                       write_history_json)
from repro.optim import make_optimizer
from repro.optim.schedules import learning_rate, scaled_base_lr
from repro.privacy import PrivacyConfig, PrivacyEngine, make_privacy


def privacy_from_args(args):
    """PrivacyConfig from --dp-*/--secure-agg; None with everything off."""
    if (args.dp_clip == 0.0 and args.dp_noise_multiplier == 0.0
            and not args.secure_agg):
        return None
    return PrivacyConfig(
        clip=args.dp_clip, noise_multiplier=args.dp_noise_multiplier,
        delta=args.dp_delta, epsilon_budget=args.dp_epsilon_budget,
        secure_agg=args.secure_agg)


def obs_from_args(args, mode):
    """Observability bundle from --trace/--metrics/--profile-dir plus the
    health monitor (--health/--halt-on-unhealthy) and the measured
    per-stage cost attribution (--measure-resources)."""
    return make_obs(trace=args.trace, metrics=args.metrics,
                    profile_dir=args.profile_dir or None,
                    health=args.health,
                    halt_on_unhealthy=args.halt_on_unhealthy,
                    measure_resources=args.measure_resources,
                    mode=mode, schedule=args.schedule, engine=args.engine,
                    codec=args.codec, seed=args.seed)


def export_obs(obs, args, hist=None):
    """Write the enabled artifacts under --obs-dir and report the paths."""
    if not obs.enabled:
        return {}
    out = pathlib.Path(args.obs_dir)
    written = obs.export(
        trace_jsonl=out / "run_trace.jsonl" if args.trace else None,
        chrome_trace=out / "run_trace.chrome.json" if args.trace else None,
        metrics_csv=out / "run_metrics.csv" if args.metrics else None,
        health_json=(out / "health.json" if obs.health is not None
                     else None),
        schedule=args.schedule, engine=args.engine, codec=args.codec)
    if args.metrics and hist is not None:
        written["history_json"] = write_history_json(
            hist, out / "run_history.json", schedule=args.schedule,
            engine=args.engine, codec=args.codec)
    for kind, path in sorted(written.items()):
        print(f"obs: wrote {kind} -> {path}")
    return written


def vit_configs(layers: int = 0, d_model: int = 0):
    """(model, SSL, training) configs for ``--mode vit``. With neither
    size given this is the published ViT-Tiny with the ``SSLConfig``
    default MoCo v3 heads and the arch's own ``TRAIN`` (remat on); either
    size given selects the reduced CPU variant (4 layers, d=64 for the one
    left out), with 256/256/64 heads."""
    if not (layers or d_model):
        return load_arch("vit-tiny"), SSLConfig(), load_train("vit-tiny")
    layers, d_model = layers or 4, d_model or 64
    cfg = reduced(load_arch("vit-tiny"), num_layers=layers, d_model=d_model,
                  num_heads=4, num_kv_heads=4, d_ff=2 * d_model)
    return (cfg, SSLConfig(proj_hidden=256, pred_hidden=256, proj_dim=64),
            TrainConfig())


def train_vit(args):
    key = jax.random.PRNGKey(args.seed)
    cfg, ssl_cfg, tc = vit_configs(args.layers, args.d_model)
    tc = dataclasses.replace(tc, batch_size=args.batch)
    fl = FLConfig(num_clients=args.clients, rounds=args.rounds,
                  local_epochs=args.local_epochs, schedule=args.schedule,
                  server_epochs=1, depth_dropout=args.depth_dropout,
                  clients_per_round=args.clients_per_round)
    kd, key = jax.random.split(key)
    images, labels = synthetic_images(kd, args.samples, 10, 32)
    if args.dirichlet_beta > 0:
        idx = dirichlet_partition(jax.device_get(labels), fl.num_clients,
                                  args.dirichlet_beta, seed=args.seed)
    else:
        idx = iid_partition(args.samples, fl.num_clients, seed=args.seed)
    aux = images[:max(args.batch, args.samples // 10)]
    sim = make_sim_from_args(args, fl.num_clients)
    obs = obs_from_args(args, "vit")
    t0 = time.time()
    with ConsoleRenderer(live=args.live) as log:
        state, hist = run_fedssl(
            cfg, ssl_cfg, fl, tc, images=images,
            client_indices=[jnp.asarray(i) for i in idx], aux_images=aux,
            key=key, log=log, engine=args.engine, codec=args.codec,
            transport_kernels=args.transport_kernels, sim=sim, obs=obs,
            privacy=privacy_from_args(args))
    export_obs(obs, args, hist=hist)
    print(f"training done in {time.time() - t0:.1f}s; "
          f"total comm {hist.total_comm / 1e6:.2f} MB analytic, "
          f"{hist.total_wire / 1e6:.2f} MB on the wire "
          f"({args.codec}: {hist.compression_ratio:.2f}x)")
    if hist.epsilon:
        print(f"privacy: eps {hist.epsilon[-1]:.4g} at delta "
              f"{args.dp_delta:g} after {len(hist.epsilon)} rounds; "
              f"mean clip fraction {np.mean(hist.clip_fraction):.2f}; "
              f"secure-agg overhead "
              f"{sum(hist.secure_agg_overhead_bytes) / 1e6:.2f} MB/client")
    if sim is not None:
        print(f"simulated fleet '{args.fleet}' / policy "
              f"'{args.round_policy}': {hist.total_wall_clock:.1f}s "
              f"wall-clock, {hist.total_device_seconds:.1f} device-s, "
              f"{hist.total_energy:.1f}J, "
              f"{hist.total_dropped} dropped client-rounds")
    enc = ssl_mod.make_vit_encoder(cfg)
    n_eval = min(args.samples // 2, 512)
    acc = fl_eval.linear_eval(
        enc, state["online"]["enc"], images[:n_eval], labels[:n_eval],
        images[n_eval:2 * n_eval], labels[n_eval:2 * n_eval],
        num_classes=10, epochs=5, batch_size=64)
    print(f"linear evaluation accuracy: {acc * 100:.2f}%")
    return acc


def train_lm(args):
    """LM-family layer-wise FedSSL on token shards (reduced arch)."""
    from repro.core.ssl import lm_ssl_loss
    from repro.models import lm as lm_mod

    key = jax.random.PRNGKey(args.seed)
    prv = make_privacy(privacy_from_args(args))
    # dedicated privacy stream: fold_in leaves the main chain untouched,
    # so DP-off runs are byte-identical to pre-privacy behavior
    k_priv = PrivacyEngine.fork_stream(key) if prv is not None else None
    cfg = reduced(load_arch(args.arch))
    S = lm_mod.num_stages(cfg)
    fl = FLConfig(num_clients=args.clients, rounds=args.rounds,
                  local_epochs=args.local_epochs, schedule=args.schedule)
    tc = TrainConfig(batch_size=args.batch, base_lr=3e-4)
    plans = sched.build_schedule(fl, S)
    opt = make_optimizer(tc)
    kd, ki, key = jax.random.split(key, 3)
    toks, labs = synthetic_tokens(kd, args.samples, args.seq_len,
                                  cfg.vocab_size)
    shards = iid_partition(args.samples, fl.num_clients, seed=args.seed)
    params = lm_mod.init_lm(ki, cfg)
    base_lr = scaled_base_lr(tc.base_lr, tc.batch_size)

    step_cache = {}

    def get_step(plan):
        sig = (plan.sub_layers, plan.active_from, plan.align)
        if sig not in step_cache:
            @jax.jit
            def train_step(params, opt_state, batch, global_params, lr):
                def loss_fn(p):
                    return lm_ssl_loss(
                        p, batch, cfg, sub_layers=sig[0], active_from=sig[1],
                        global_params=global_params if sig[2] else None,
                        align_weight=0.01 if sig[2] else 0.0)
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
                from repro.federated.masks import stage_update_mask
                mask = stage_update_mask(params, sig[0], sig[1])
                p2, o2 = opt.update(g, opt_state, params, lr, mask)
                return p2, o2, m
            step_cache[sig] = train_step
        return step_cache[sig]

    w = aggregate.client_weights([len(shards[i])
                                  for i in range(fl.num_clients)])

    def batch_start(ix, b):
        """Shard-local start of local step ``b`` — the single source of
        truth for batch selection, shared by both engines."""
        return (b * tc.batch_size) % max(1, len(ix) - tc.batch_size)

    use_vmap = args.engine == "vmap"
    obs = obs_from_args(args, "lm")
    wire = transport_mod.Transport(args.codec,
                                   kernels=args.transport_kernels, obs=obs,
                                   privacy=prv)
    all_clients = list(range(fl.num_clients))
    secure = prv is not None and prv.cfg.secure_agg
    if use_vmap:
        from repro.data.partition import stack_shards
        from repro.launch.steps import make_fl_round_program
        if min(len(s) for s in shards) < tc.batch_size:
            raise SystemExit("--engine vmap needs every shard >= batch size")
        stacked, _ = stack_shards({"tokens": toks, "labels": labs},
                                  [jnp.asarray(s) for s in shards])
        nbs = [max(1, len(s) // tc.batch_size) for s in shards]
        T = max(nbs) * fl.local_epochs
        # replay the sequential loop's deterministic batch slices as
        # shard-local gather indices; ragged clients are masked out
        batch_idx = np.zeros((fl.num_clients, T, tc.batch_size), np.int32)
        valid = np.zeros((fl.num_clients, T), bool)
        for ci, ix in enumerate(shards):
            for b in range(nbs[ci] * fl.local_epochs):
                start = batch_start(ix, b)
                batch_idx[ci, b] = np.arange(start, start + tc.batch_size)
                valid[ci, b] = True
        batch_idx, valid = jnp.asarray(batch_idx), jnp.asarray(valid)
        step_keys = jnp.zeros((fl.num_clients, T, 2), jnp.uint32)
        round_cache = {}

        def get_round(plan, spec, fedavg=True):
            sig = (plan.sub_layers, plan.active_from, plan.align, spec.sig,
                   fedavg)
            if sig not in round_cache:
                wt = wire.make_wire_transform(spec)
                round_cache[sig] = make_fl_round_program(
                    cfg, tc, sub_layers=plan.sub_layers,
                    active_from=plan.active_from, align=plan.align,
                    wire_transform=lambda outs, bc, res: wt(
                        outs, bc["server"], bc["params"], res),
                    fedavg=fedavg)[0]
            return round_cache[sig]

    hist = []
    wire_mb = 0.0
    tracer, log = obs.tracer, ConsoleRenderer(live=args.live)
    obs.start_profiler()
    with tracer.span("run", cat="fl", mode="lm-fedssl",
                     schedule=fl.schedule, engine=args.engine,
                     codec=wire.codec.name, kernels=args.transport_kernels,
                     rounds=fl.rounds, clients=fl.num_clients):
        for plan in plans:
            round_span = tracer.span("round", cat="fl",
                                     round=plan.round_idx, stage=plan.stage)
            t_round = time.perf_counter()
            with round_span:
                if plan.new_stage and fl.weight_transfer:
                    params = sched.transfer_model(params, cfg, plan.stage)
                lr = float(learning_rate(plan.round_idx, fl.rounds, base_lr,
                                         tc.lr_schedule))
                # both directions route through the wire transport: clients
                # train from the decoded broadcast, FedAvg consumes decoded
                # uploads
                dparams, down = wire.broadcast(params, plan)
                global_params = (jax.tree.map(jnp.copy, dparams)
                                 if plan.align else None)
                train_span = tracer.span("local_train", cat="fl",
                                         engine=args.engine,
                                         clients=fl.num_clients)
                spec = (wire.plan_specs(params, plan)["upload"]
                        if (use_vmap or prv is not None) else None)
                if prv is not None:
                    k_noise, mask_seed = PrivacyEngine.round_keys(
                        k_priv, plan.round_idx)
                if use_vmap:
                    up = dict(wire.upload_stats(spec))
                    res = wire.gather_residuals(all_clients, spec)
                    with train_span:
                        result, lvec, new_res, scales = get_round(
                            plan, spec, fedavg=not secure)(
                            {"params": dparams,
                             "global_params": global_params,
                             "server": params},
                            stacked, batch_idx, step_keys, valid, w,
                            jnp.float32(lr), res)
                    wire.store_residuals(all_clients, spec, new_res)
                    if secure:
                        # unstack the decoded client axis and FedAvg
                        # through the masked fixed-point pipeline
                        trees = [jax.tree.map(lambda a, i=i: a[i], result)
                                 for i in range(fl.num_clients)]
                        params = prv.secure_fedavg(
                            trees, np.asarray(w), all_clients, spec=spec,
                            transport=wire, base=params, seed=mask_seed)
                    else:
                        params = result
                    up["clip_fraction"] = float(
                        np.mean(np.asarray(scales, np.float32) < 1.0))
                    losses = [float(x) for x in np.asarray(lvec)]
                else:
                    step = get_step(plan)
                    outs, losses = [], []
                    with train_span:
                        for ci in range(fl.num_clients):
                            p_i = jax.tree.map(jnp.asarray, dparams)
                            o_i = opt.init(p_i)
                            ix = shards[ci]
                            nb = max(1, len(ix) // tc.batch_size)
                            for b in range(nb * fl.local_epochs):
                                sel = ix[batch_start(ix, b):][:tc.batch_size]
                                batch = {"tokens": toks[sel],
                                         "labels": labs[sel]}
                                p_i, o_i, m = step(p_i, o_i, batch,
                                                   global_params,
                                                   jnp.float32(lr))
                            outs.append(p_i)
                            losses.append(float(m["loss"]))
                    if secure:
                        trees, up = wire.decode_uploads(
                            params, outs, all_clients, plan,
                            ref_online=dparams)
                        params = prv.secure_fedavg(
                            trees, np.asarray(w), all_clients, spec=spec,
                            transport=wire, base=params, seed=mask_seed)
                    else:
                        params, up = wire.aggregate_uploads(
                            params, outs, all_clients, plan, w,
                            ref_online=dparams)
                eps = None
                if prv is not None:
                    if prv.noise_enabled:
                        params = prv.add_noise(
                            params, spec, wire, k_noise,
                            prv.sigma(float(np.max(np.asarray(w)))))
                    # full participation every round: q = 1
                    prv.accountant.observe_round(1.0)
                    eps = float(prv.accountant.epsilon(prv.cfg.delta))
                wire_mb += (down["wire_bytes"] + up["wire_bytes"]) / 1e6
                hist.append(sum(losses) / len(losses))
                cb = comm.round_comm_bytes(params, plan)
                round_span.set(loss=hist[-1], lr=lr,
                               download_bytes=cb["download"],
                               upload_bytes=cb["upload"],
                               wire_download_bytes=down["wire_bytes"],
                               wire_upload_bytes=up["wire_bytes"])
                if prv is not None:
                    round_span.set(
                        epsilon=eps,
                        clip_fraction=float(up.get("clip_fraction", 0.0)),
                        secure_agg_overhead_bytes=prv.secure_overhead_bytes(
                            spec, wire.wire_bytes(spec)))
            if obs.enabled:
                met = obs.metrics
                met.counter("fl.rounds").inc()
                met.counter("comm.download_bytes").inc(cb["download"])
                met.counter("comm.upload_bytes").inc(cb["upload"])
                met.counter("wire.download_bytes").inc(down["wire_bytes"])
                met.counter("wire.upload_bytes").inc(up["wire_bytes"])
                met.histogram("round.loss").observe(hist[-1])
                met.histogram("round.host_seconds").observe(
                    time.perf_counter() - t_round)
            log(format_round_line(
                plan.round_idx, fl.rounds, plan.stage, hist[-1], lr=lr,
                wire_mb=(down["wire_bytes"] + up["wire_bytes"]) / 1e6,
                extra=f" eps {eps:.3g}" if prv is not None
                and prv.dp else ""))
            if obs.health is not None:
                for alert in obs.health.observe_round(
                        plan.round_idx, loss=hist[-1],
                        compression_ratio=(cb["download"] + cb["upload"])
                        / max(1, down["wire_bytes"] + up["wire_bytes"]),
                        participants=fl.num_clients,
                        new_stage=plan.new_stage):
                    tracer.instant("health." + alert.kind, cat="health",
                                   level=alert.level, round=plan.round_idx,
                                   message=alert.message)
                    log(f"health[{alert.level}] round {plan.round_idx}: "
                        f"{alert.message}")
                if obs.health.should_halt:
                    tracer.instant("health.halt", cat="health",
                                   round=plan.round_idx)
                    log(f"health: fatal alert; halting after round "
                        f"{plan.round_idx + 1}/{fl.rounds}")
                    break
            if (prv is not None and prv.cfg.epsilon_budget > 0.0
                    and eps > prv.cfg.epsilon_budget):
                log(f"privacy budget exhausted: eps {eps:.4g} > "
                    f"{prv.cfg.epsilon_budget:.4g} after round "
                    f"{plan.round_idx + 1}/{fl.rounds}; halting")
                break
    obs.stop_profiler()
    log.close()
    export_obs(obs, args)
    print(f"final loss {hist[-1]:.4f} (start {hist[0]:.4f}); "
          f"{wire_mb:.2f} MB/client on the wire ({args.codec})")
    if prv is not None and prv.dp:
        print(f"privacy: eps {eps:.4g} at delta {prv.cfg.delta:g} "
              f"after {len(hist)} rounds")
    return params, hist


def make_sim_from_args(args, num_clients):
    """Build the fleet simulator from CLI flags; None when --fleet unset."""
    if not args.fleet:
        if args.round_policy != "synchronous":
            raise SystemExit(
                "--round-policy needs --fleet (one of "
                + ", ".join(fleet_mod.PROFILES) + ")")
        return None
    kw = {}
    if args.round_policy == "deadline":
        kw = {"overcommit": args.overcommit}
        if args.deadline_s > 0:
            kw["deadline_s"] = args.deadline_s
    elif args.round_policy == "buffered-async":
        kw = {"buffer": args.async_buffer, "alpha": args.staleness_alpha}
    return sim_mod.make_sim(args.fleet, args.round_policy,
                            num_clients=num_clients, seed=args.seed, **kw)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("vit", "lm"), default="vit")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--schedule", default="lw_fedssl",
                    choices=sched.SCHEDULES)
    ap.add_argument("--engine", default="sequential",
                    choices=("sequential", "vmap"),
                    help="round engine: per-client loop (reference) or "
                         "one jit'd vmapped program per round")
    ap.add_argument("--codec", default="fp32",
                    help="wire compression codec for downloads/uploads: "
                         "fp32 (identity), fp16, bf16, int8 (per-channel "
                         "quantization), topk[:frac] (sparsification with "
                         "error feedback, e.g. topk:0.05)")
    ap.add_argument("--transport-kernels", default="xla",
                    choices=transport_mod.TRANSPORT_KERNELS,
                    help="wire-path engine: xla (jit'd slice/concat "
                         "reference) or pallas (fused pack/codec kernels "
                         "— docs/kernels.md)")
    ap.add_argument("--fleet", default="",
                    choices=("",) + fleet_mod.PROFILES,
                    help="simulate a heterogeneous device fleet drawn from "
                         "this named profile (docs/simulation.md); empty = "
                         "no simulation")
    ap.add_argument("--round-policy", default="synchronous",
                    choices=sim_mod.POLICIES,
                    help="round scheduling policy over the simulated "
                         "fleet: synchronous (wait for all), deadline "
                         "(overcommit + drop stragglers), buffered-async "
                         "(staleness-weighted FedBuff aggregation)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="fixed round deadline in simulated seconds "
                         "(0 = adaptive: the cohort's 60th percentile)")
    ap.add_argument("--overcommit", type=float, default=1.5,
                    help="deadline policy: sample this factor more "
                         "clients, clamped to the population")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="buffered-async: aggregate once this many "
                         "updates arrived (0 = half the cohort)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="buffered-async: (1+staleness)^-alpha weight "
                         "discount")
    ap.add_argument("--dp-clip", type=float, default=0.0,
                    help="client-level DP: L2 clip on each client's "
                         "stage-payload update (0 = off; 'inf' runs the "
                         "clipping machinery as an exact pass-through)")
    ap.add_argument("--dp-noise-multiplier", type=float, default=0.0,
                    help="client-level DP: noise multiplier z — server "
                         "adds N(0, (z*clip*max_w)^2) to the aggregate; "
                         "requires a finite --dp-clip > 0")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="delta of the reported (eps, delta) guarantee")
    ap.add_argument("--dp-epsilon-budget", type=float, default=0.0,
                    help="halt training once cumulative eps exceeds this "
                         "(0 = unlimited)")
    ap.add_argument("--secure-agg", action="store_true",
                    help="pairwise-mask secure aggregation: FedAvg runs "
                         "as a masked fixed-point sum, the server never "
                         "sees an individual update (docs/privacy.md)")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--clients-per-round", type=int, default=0)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--samples", type=int, default=1024)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--layers", type=int, default=0,
                    help="vit: encoder depth of the reduced CPU variant "
                         "(0 with --d-model 0 = the published ViT-Tiny)")
    ap.add_argument("--d-model", type=int, default=0,
                    help="vit: width of the reduced CPU variant (0 with "
                         "--layers 0 = the published ViT-Tiny)")
    ap.add_argument("--depth-dropout", type=float, default=0.0)
    ap.add_argument("--dirichlet-beta", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", action="store_true",
                    help="record a span trace of the run and write "
                         "run_trace.jsonl + run_trace.chrome.json (the "
                         "latter loads in Perfetto / chrome://tracing) "
                         "under --obs-dir; analyze with `python -m "
                         "repro.launch.trace` (docs/observability.md)")
    ap.add_argument("--metrics", action="store_true",
                    help="record typed counters/gauges/histograms and "
                         "write run_metrics.csv + run_history.json under "
                         "--obs-dir")
    ap.add_argument("--health", action="store_true",
                    help="attach the streaming health monitor (NaN/inf "
                         "loss, z-score loss spikes, compression-ratio "
                         "and straggler drop-rate drift, jit-recompile "
                         "storms) and write a schema-validated "
                         "health.json under --obs-dir "
                         "(docs/observability.md)")
    ap.add_argument("--halt-on-unhealthy", action="store_true",
                    help="stop training on a fatal health alert "
                         "(implies --health)")
    ap.add_argument("--measure-resources", action="store_true",
                    help="AOT-lower each new stage's round program and "
                         "attach measured cost_analysis attributes "
                         "(res.*) to the stage-opening round span; a few "
                         "seconds per stage")
    ap.add_argument("--profile-dir", default="",
                    help="also capture a jax.profiler (XLA-level) trace "
                         "into this directory; spans are host-level")
    ap.add_argument("--obs-dir", default="results",
                    help="directory for observability artifacts")
    ap.add_argument("--live", action="store_true",
                    help="render round progress as a single live-updating "
                         "console line instead of one line per round")
    args = ap.parse_args()
    try:
        transport_mod.make_codec(args.codec)
        make_privacy(privacy_from_args(args))
    except ValueError as e:
        ap.error(str(e))
    if args.mode == "lm" and args.fleet:
        ap.error("--fleet simulation currently drives the vit driver "
                 "(repro.federated.driver); use --mode vit")
    if args.mode == "vit":
        train_vit(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
