"""End-to-end federated SSL driver (paper Algorithms 1 + 2).

Simulates the full FL process on one host: N clients with IID/Dirichlet
shards, per-round client sampling, local MoCo v3 (or SimCLR/BYOL) training
with the stage schedule, FedAvg aggregation, server-side calibration and
communication accounting.

The per-round "train participants, aggregate" middle is delegated to an
execution engine (``repro.federated.engine``): ``sequential`` loops over
clients one at a time (the numerical reference), ``vmap`` stacks the
sampled clients on a leading axis and runs the whole round — local steps
and FedAvg — as one jit'd program. The stage schedule, LR, calibration and
comm-accounting logic here is shared by both engines unchanged.

Every download and upload routes through the wire transport
(``repro.federated.transport``): the round plan's stage payload is packed
into flat buffers, pushed through the configured compression codec, and
training/aggregation consume the *decoded* payloads, so codec error
propagates realistically. ``FLHistory`` records both the analytic byte
counts (``comm.round_comm_bytes``) and the measured wire bytes; with the
fp32 identity codec the two are equal and training is bit-identical to
handing pytrees around directly.

Privacy (``repro.privacy``, off by default): pass ``privacy=
PrivacyConfig(...)`` for client-level DP-FedAvg — per-client update
clipping inside both engines' wire paths, one calibrated Gaussian draw on
the aggregate, RDP accounting into ``FLHistory.epsilon`` with an optional
hard ``epsilon_budget`` stop — and/or pairwise-mask secure aggregation,
which replaces the float FedAvg with a masked fixed-point sum at the
aggregation boundary (composing with every codec, schedule, engine and
round policy). See docs/privacy.md.

Observability (``repro.obs``, off by default): pass ``obs=make_obs(...)``
and every round becomes a span tree — ``run > round > {download,
local_train, calibrate}`` with engine/transport child spans — annotated
with the analytic and wire byte counts, loss, LR and participation, while
the metrics registry accumulates wire-byte counters, round-time
histograms and a jit-recompile counter read off the engine/transport
compile caches. The trace CLI (``python -m repro.launch.trace``)
regenerates the paper's comm tables from those spans alone. With the
default ``NOOP_OBS`` every hook is a no-op and training output is
bit-identical to the uninstrumented driver.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.federated import aggregate, comm, server
from repro.federated import engine as engine_mod
from repro.federated import transport as transport_mod
from repro.obs import NOOP_OBS, format_round_line
from repro.obs import resources as obs_resources
from repro.obs.trace import is_tracing
from repro.privacy import PrivacyEngine, make_privacy
from repro.optim import make_optimizer
from repro.optim.schedules import learning_rate, scaled_base_lr

# v2 added the privacy fields (epsilon / clip_fraction /
# secure_agg_overhead_bytes); v1 dicts still load, the new fields default
# to empty lists
HISTORY_VERSION = 2
_COMPAT_VERSIONS = (1, 2)


@dataclass
class FLHistory:
    loss: List[float] = field(default_factory=list)
    round_stage: List[int] = field(default_factory=list)
    # analytic per-client byte counts (leaf shapes x round plan, comm.py)
    download_bytes: List[int] = field(default_factory=list)
    upload_bytes: List[int] = field(default_factory=list)
    # measured per-client wire bytes: size of the arrays the transport
    # codec actually put on the wire this round
    wire_download_bytes: List[int] = field(default_factory=list)
    wire_upload_bytes: List[int] = field(default_factory=list)
    # fleet-simulator accounting (populated only when a Simulation is
    # passed to run_fedssl; empty lists otherwise)
    round_wall_clock: List[float] = field(default_factory=list)
    device_seconds: List[float] = field(default_factory=list)
    energy_joules: List[float] = field(default_factory=list)
    dropped_clients: List[int] = field(default_factory=list)
    participants: List[tuple] = field(default_factory=list)
    # privacy accounting (populated only when run_fedssl gets privacy=...;
    # empty lists otherwise): cumulative (ε, δ) after each round, fraction
    # of participants whose update was clipped, per-client secure-agg wire
    # overhead in bytes
    epsilon: List[float] = field(default_factory=list)
    clip_fraction: List[float] = field(default_factory=list)
    secure_agg_overhead_bytes: List[int] = field(default_factory=list)

    @property
    def total_comm(self) -> int:
        return sum(self.download_bytes) + sum(self.upload_bytes)

    @property
    def total_wire(self) -> int:
        return sum(self.wire_download_bytes) + sum(self.wire_upload_bytes)

    @property
    def compression_ratio(self) -> float:
        """Measured compression: analytic (uncompressed) bytes over wire
        bytes. 1.0 for the identity codec; NaN when nothing has been on
        the wire yet (an empty history has no ratio, not a huge one)."""
        if self.total_wire == 0:
            return float("nan")
        return self.total_comm / self.total_wire

    @property
    def total_wall_clock(self) -> float:
        return sum(self.round_wall_clock)

    @property
    def total_device_seconds(self) -> float:
        return sum(self.device_seconds)

    @property
    def total_energy(self) -> float:
        return sum(self.energy_joules)

    @property
    def total_dropped(self) -> int:
        return sum(self.dropped_clients)

    def wall_clock_to_loss(self, target: float):
        """Cumulative simulated seconds until the round-mean loss first
        reaches ``target``; None if it never does (or no simulation ran)."""
        t = 0.0
        for wall, loss in zip(self.round_wall_clock, self.loss):
            t += wall
            if loss <= target:
                return t
        return None

    # -- JSON round-trip: the one serialization traces, benches and
    # -- checkpoints share (versioned, keyed by field name) ------------------
    def to_dict(self) -> Dict[str, Any]:
        fields: Dict[str, list] = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            fields[f.name] = ([list(t) for t in v]
                              if f.name == "participants" else list(v))
        return {"version": HISTORY_VERSION, "fields": fields}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FLHistory":
        if d.get("version") not in _COMPAT_VERSIONS:
            raise ValueError(f"unsupported FLHistory version "
                             f"{d.get('version')!r} "
                             f"(have {_COMPAT_VERSIONS})")
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {}
        for name, vals in d.get("fields", {}).items():
            if name not in known:
                raise ValueError(f"unknown FLHistory field '{name}'")
            kw[name] = ([tuple(v) for v in vals]
                        if name == "participants" else list(vals))
        return cls(**kw)


def run_fedssl(model_cfg, ssl_cfg, fl, train_cfg, *, images, client_indices,
               aux_images=None, key=None, encoder=None, image_size: int = 32,
               log=None, engine: str = "sequential",
               codec: str = "fp32", transport_kernels: str = "xla",
               sim=None, obs=None, privacy=None) -> tuple:
    """Run the FL process; returns (final_state, FLHistory).

    images: (n, H, W, 3) pooled training pool; client_indices: list of index
    arrays (one per client); aux_images: D_g for server calibration;
    engine: "sequential" (reference) or "vmap" (one dispatch per round);
    codec: wire compression (transport.CODECS — fp32/fp16/bf16/int8/topk);
    transport_kernels: wire-path engine (transport.TRANSPORT_KERNELS) —
    "xla" (jit'd slice/concat reference) or "pallas" (fused pack/codec
    kernels; fp32/fp16/bf16 bit-identical, int8/topk within 1e-6);
    sim: optional ``simulation.Simulation`` (fleet + round policy). With
    ``sim=None`` — or the synchronous policy over a uniform fleet — the
    training numerics are bit-identical to the pre-simulator driver; other
    policies change who trains and how updates aggregate, and ``FLHistory``
    gains per-round wall-clock / device-seconds / energy / drop counts;
    obs: optional ``repro.obs.Observability`` (spans, metrics, profiler).
    Defaults to the no-op bundle — tracing never changes training numerics;
    privacy: optional ``repro.privacy.PrivacyConfig`` (or an existing
    ``PrivacyEngine``) — client-level DP-FedAvg clipping/noise, RDP
    accounting into ``FLHistory.epsilon`` (``--dp-epsilon-budget`` halts
    training when exceeded) and pairwise-mask secure aggregation. The
    privacy RNG is a dedicated stream folded off the run key, so DP-off
    runs are byte-identical to passing ``privacy=None``.
    """
    obs = obs if obs is not None else NOOP_OBS
    tracer, met = obs.tracer, obs.metrics
    key = key if key is not None else jax.random.PRNGKey(fl.seed)
    prv = make_privacy(privacy)
    k_privacy = PrivacyEngine.fork_stream(key) if prv is not None else None
    if encoder is None:
        encoder = ssl_mod.make_vit_encoder(model_cfg, image_size,
                                           remat=train_cfg.remat)
    k_init, key = jax.random.split(key)
    state = ssl_mod.ssl_init(k_init, encoder, ssl_cfg)
    opt = make_optimizer(train_cfg)
    plans = sched.build_schedule(fl, encoder.num_stages)
    base_lr = scaled_base_lr(train_cfg.base_lr, train_cfg.batch_size)
    hist = FLHistory()

    counts = [len(ix) for ix in client_indices]
    wire = transport_mod.Transport(codec, include_heads=fl.include_heads,
                                   kernels=transport_kernels, obs=obs,
                                   privacy=prv)
    eng = engine_mod.make_engine(
        engine, encoder=encoder, ssl_cfg=ssl_cfg, opt=opt, fl=fl,
        train_cfg=train_cfg, images=images, client_indices=client_indices,
        transport=wire, obs=obs)
    if sim is not None:
        sim.obs = obs
        # ViT patch grid prices the per-step FLOPs (4x4 patches)
        sim.prepare(model_cfg, num_stages=encoder.num_stages,
                    counts=[len(ix) for ix in client_indices],
                    batch=train_cfg.batch_size,
                    tokens=(image_size // 4) ** 2,
                    local_epochs=fl.local_epochs)

    calib_cache: Dict[int, Any] = {}

    def get_calib(sub_layers):
        if sub_layers not in calib_cache:
            calib_cache[sub_layers] = server.make_calibration_step(
                encoder, ssl_cfg, opt, sub_layers=sub_layers)
        return calib_cache[sub_layers]

    # stage-relative step counters for the cyclic LR strategy
    stage_start = {}
    for p in plans:
        stage_start.setdefault(p.stage, p.round_idx)
    stage_lengths = {s: sum(1 for p in plans if p.stage == s)
                     for s in set(p.stage for p in plans)}

    obs.start_profiler()
    jit_entries = 0
    run_span = tracer.span(
        "run", cat="fl", mode="fedssl", schedule=fl.schedule, engine=engine,
        codec=wire.codec.name, kernels=transport_kernels, rounds=fl.rounds,
        clients=fl.num_clients, sim=sim.policy.name if sim else None)
    with run_span:
        for plan in plans:
            host_t0 = time.perf_counter()
            round_span = tracer.span("round", cat="fl",
                                     round=plan.round_idx, stage=plan.stage)
            with round_span:
                if plan.new_stage:
                    tracer.instant("stage_transition", cat="fl",
                                   stage=plan.stage)
                    if sim is not None:
                        sim.begin_stage()
                    state = server.begin_stage(
                        state, plan.stage,
                        weight_transfer=fl.weight_transfer)
                    if obs.measure_resources:
                        # measured cost attribution for the stage's round
                        # program (AOT lowering only — never compiles, so
                        # the jit.recompiles counter stays untouched)
                        with tracer.span("resources.measure", cat="obs",
                                         stage=plan.stage):
                            round_span.set(**obs_resources.stage_cost_attrs(
                                eng, plan))
                lr = float(learning_rate(
                    plan.round_idx, fl.rounds, base_lr,
                    train_cfg.lr_schedule,
                    stage_step=plan.round_idx - stage_start[plan.stage],
                    stage_total=stage_lengths[plan.stage],
                    warmup_steps=train_cfg.warmup_steps))
                key, ks = jax.random.split(key)
                # with the default overcommit (1.0) this is byte-for-byte
                # the historical sampling call — same key, same cohort
                with tracer.span("fl.sample", cat="fl"):
                    cohort = server.sample_clients(
                        ks, fl.num_clients, fl.clients_per_round,
                        overcommit=sim.overcommit if sim is not None
                        else 1.0)
                # download direction: clients (and the alignment loss's
                # global model) see the wire-decoded broadcast, not the
                # server pytree
                with tracer.span("download", cat="fl"):
                    dstate, down = server.broadcast_download(state, plan,
                                                             wire)
                global_enc = (jax.tree.map(jnp.copy,
                                           dstate["online"]["enc"])
                              if plan.align else None)
                outcome = None
                up_spec = (wire.plan_specs(state["online"], plan)["upload"]
                           if (sim is not None or prv is not None) else None)
                if sim is not None:
                    outcome = sim.begin_round(
                        plan, cohort, down_bytes=down["wire_bytes"],
                        up_bytes=wire.upload_stats(up_spec)["wire_bytes"])
                    participants = list(outcome.train_ids)
                else:
                    participants = cohort
                # privacy RNG: dedicated stream, folded per round — never
                # touches the main chain split above/below
                if prv is not None:
                    k_noise, mask_seed = PrivacyEngine.round_keys(
                        k_privacy, plan.round_idx)
                secure = prv is not None and prv.cfg.secure_agg
                # per-participant keys are split here, identically for
                # both engines, so the main RNG chain (and the calibration
                # key below) is engine-independent
                client_keys = []
                for _ in participants:
                    key, kc = jax.random.split(key)
                    client_keys.append(kc)
                train_span = tracer.span("local_train", cat="fl",
                                         participants=len(participants))
                if sim is not None and sim.policy.needs_client_trees:
                    # buffered-async: the engine returns per-client decoded
                    # trees; the policy buffers them and aggregates
                    # arrivals staleness-weighted (possibly rounds after
                    # they trained). Secure aggregation injects its masked
                    # FedAvg into the buffer flush (masks derived over each
                    # flush's arrival set — survivor-set re-masking).
                    with train_span:
                        if participants:
                            trees, losses, up = eng.run_round(
                                dstate, plan, participants, client_keys,
                                lr, global_enc,
                                server_online=state["online"],
                                collect=True)
                        else:  # every sampled candidate was busy/offline
                            trees, losses = [], []
                            up = wire.upload_stats(up_spec)
                    new_online, outcome = sim.complete_round_async(
                        outcome, trees,
                        agg_fn=prv.make_secure_agg_fn(
                            wire, up_spec, state["online"], mask_seed)
                        if secure else None)
                elif secure:
                    # synchronous/deadline secure round: collect decoded
                    # per-client trees, FedAvg through the masked
                    # fixed-point pipeline instead of the engine's fused
                    # float aggregation
                    with train_span:
                        trees, losses, up = eng.run_round(
                            dstate, plan, participants, client_keys, lr,
                            global_enc, server_online=state["online"],
                            collect=True)
                    w = aggregate.client_weights(
                        [counts[i] for i in participants])
                    new_online = prv.secure_fedavg(
                        trees, np.asarray(w), participants, spec=up_spec,
                        transport=wire, base=state["online"],
                        seed=mask_seed)
                    if sim is not None:
                        outcome = sim.complete_round(outcome)
                else:
                    with train_span:
                        new_online, losses, up = eng.run_round(
                            dstate, plan, participants, client_keys, lr,
                            global_enc, server_online=state["online"])
                    if sim is not None:
                        outcome = sim.complete_round(outcome)
                if prv is not None and prv.noise_enabled:
                    # one server-side Gaussian draw on the aggregated
                    # payload, σ = z·C·max_w (sensitivity of the weighted
                    # mean); the async policy reports its staleness
                    # weights, every other path is sample-count FedAvg
                    if outcome is not None and outcome.weights:
                        max_w = max(outcome.weights)
                    else:
                        agg_ids = (list(outcome.aggregated)
                                   if outcome is not None else participants)
                        max_w = float(np.max(np.asarray(
                            aggregate.client_weights(
                                [counts[i] for i in agg_ids]))))
                    new_online = prv.add_noise(new_online, up_spec, wire,
                                               k_noise, prv.sigma(max_w))
                state = {**state, "online": new_online}
                if plan.server_calibrate and aux_images is not None:
                    key, kg = jax.random.split(key)
                    with tracer.span("calibrate", cat="fl",
                                     sub_layers=plan.sub_layers):
                        state = server.server_calibrate(
                            state, aux_images, get_calib(plan.sub_layers),
                            opt, epochs=fl.server_epochs,
                            batch_size=train_cfg.batch_size, key=kg, lr=lr)
                cb = comm.round_comm_bytes(state["online"], plan,
                                           include_heads=fl.include_heads)
                if losses:
                    hist.loss.append(sum(losses) / len(losses))
                else:  # async round with no launches: carry the mean fwd
                    hist.loss.append(hist.loss[-1] if hist.loss
                                     else float("nan"))
                hist.round_stage.append(plan.stage)
                hist.download_bytes.append(cb["download"])
                hist.upload_bytes.append(cb["upload"])
                hist.wire_download_bytes.append(down["wire_bytes"])
                hist.wire_upload_bytes.append(up["wire_bytes"])
                sim_log = ""
                if outcome is not None:
                    hist.round_wall_clock.append(outcome.wall_clock_s)
                    hist.device_seconds.append(outcome.device_seconds)
                    hist.energy_joules.append(outcome.energy_j)
                    hist.dropped_clients.append(len(outcome.dropped))
                    hist.participants.append(tuple(participants))
                    sim_log = (f" sim {outcome.wall_clock_s:.1f}s "
                               f"dropped {len(outcome.dropped)}")
                eps = None
                if prv is not None:
                    # account the *sampled* cohort (Poisson-style q =
                    # cohort / population), not the survivor set — dropped
                    # clients were still contacted
                    prv.accountant.observe_round(
                        len(cohort) / max(1, fl.num_clients))
                    eps = float(prv.accountant.epsilon(prv.cfg.delta))
                    hist.epsilon.append(eps)
                    hist.clip_fraction.append(
                        float(up.get("clip_fraction", 0.0)))
                    hist.secure_agg_overhead_bytes.append(
                        prv.secure_overhead_bytes(up_spec,
                                                  wire.wire_bytes(up_spec)))
                    sim_log += (f" eps {eps:.3g}" if prv.dp else "")
                round_span.set(
                    loss=hist.loss[-1], lr=lr,
                    download_bytes=cb["download"],
                    upload_bytes=cb["upload"],
                    wire_download_bytes=down["wire_bytes"],
                    wire_upload_bytes=up["wire_bytes"],
                    participants=len(participants),
                    dropped=len(outcome.dropped) if outcome else 0)
                if is_tracing(tracer):
                    # live watermark (mem.* attrs are excluded from
                    # Tracer.structure(): environment, not structure)
                    round_span.set(**obs_resources.memory_span_attrs())
                if prv is not None:
                    round_span.set(
                        epsilon=eps,
                        clip_fraction=hist.clip_fraction[-1],
                        secure_agg_overhead_bytes=hist
                        .secure_agg_overhead_bytes[-1])
            round_recompiles = 0
            if obs.enabled:
                met.counter("fl.rounds").inc()
                met.counter("comm.download_bytes").inc(cb["download"])
                met.counter("comm.upload_bytes").inc(cb["upload"])
                met.counter("wire.download_bytes").inc(down["wire_bytes"])
                met.counter("wire.upload_bytes").inc(up["wire_bytes"])
                met.histogram("round.host_seconds").observe(
                    time.perf_counter() - host_t0)
                met.histogram("round.loss").observe(hist.loss[-1])
                if outcome is not None:
                    met.histogram("sim.round_wall_clock_s").observe(
                        outcome.wall_clock_s)
                    met.counter("sim.energy_j").inc(outcome.energy_j)
                    met.counter("sim.dropped_clients").inc(
                        len(outcome.dropped))
                if prv is not None:
                    met.gauge("privacy.epsilon").set(eps)
                    met.histogram("privacy.clip_fraction").observe(
                        hist.clip_fraction[-1])
                    met.counter("privacy.secure_agg_overhead_bytes").inc(
                        hist.secure_agg_overhead_bytes[-1])
                entries = (eng.compile_cache_size()
                           + wire.compile_cache_size())
                if entries > jit_entries:
                    round_recompiles = entries - jit_entries
                    met.counter("jit.recompiles").inc(round_recompiles)
                    jit_entries = entries
                met.gauge("jit.cache_entries").set(jit_entries)
            if log:
                log(format_round_line(
                    plan.round_idx, fl.rounds, plan.stage, hist.loss[-1],
                    lr=lr, down_mb=cb["download"] / 1e6,
                    up_mb=cb["upload"] / 1e6,
                    wire_mb=(down["wire_bytes"] + up["wire_bytes"]) / 1e6,
                    extra=sim_log))
            if obs.health is not None:
                ratio = ((cb["download"] + cb["upload"])
                         / max(1, down["wire_bytes"] + up["wire_bytes"]))
                for alert in obs.health.observe_round(
                        plan.round_idx, loss=hist.loss[-1],
                        compression_ratio=ratio,
                        dropped=len(outcome.dropped) if outcome else 0,
                        participants=len(participants),
                        recompiles=round_recompiles,
                        new_stage=plan.new_stage):
                    tracer.instant(
                        "health." + alert.kind, cat="health",
                        level=alert.level, round=plan.round_idx,
                        value=(float(alert.value)
                               if np.isfinite(alert.value) else None),
                        message=alert.message)
                    if log:
                        log(f"health[{alert.level}] round "
                            f"{plan.round_idx}: {alert.message}")
                if obs.health.should_halt:
                    tracer.instant("health.halt", cat="health",
                                   round=plan.round_idx)
                    if log:
                        log(f"health: fatal alert; halting after round "
                            f"{plan.round_idx + 1}/{fl.rounds}")
                    break
            if (prv is not None and prv.cfg.epsilon_budget > 0.0
                    and eps > prv.cfg.epsilon_budget):
                tracer.instant("privacy.budget_exhausted", cat="fl",
                               round=plan.round_idx, epsilon=eps,
                               budget=prv.cfg.epsilon_budget)
                if log:
                    log(f"privacy budget exhausted: eps {eps:.4g} > "
                        f"{prv.cfg.epsilon_budget:.4g} after round "
                        f"{plan.round_idx + 1}/{fl.rounds}; halting")
                break
    if obs.enabled:
        met.gauge("wire.compression_ratio").set(hist.compression_ratio)
    obs.stop_profiler()
    return state, hist
