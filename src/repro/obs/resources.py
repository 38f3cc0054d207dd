"""Measured resource attribution from the compiled XLA round programs.

The analytic roofline (``repro.roofline.client_costs``) *predicts* the
paper's memory/GFLOPs/comm reductions from the ViT config; this module
*measures* them from the programs the engines actually lower and run:

  FLOPs    ``Lowered.cost_analysis()`` of each engine's round unit per
           distinct plan signature. The ViT layer scans are fully
           unrolled while lowering (``unrolled_scans``) because XLA's
           HLO cost analysis counts a rolled while-loop body once — the
           rolled programs we *run* would under-count by the trip count.
           Lowering needs no XLA compile, so a whole schedule's
           signatures measure in seconds.
  memory   ``Compiled.memory_analysis()`` (argument/output/temp/peak
           bytes). Compilation is the expensive step (~tens of seconds
           per program on one CPU), so only the signature the analytic
           model predicts as the schedule's peak is compiled.
  live     ``device.memory_stats()`` watermarks on accelerators, RSS
           from ``/proc/self`` on CPU — cheap enough for the driver to
           attach to every round span (``mem.*`` attributes, excluded
           from ``Tracer.structure()`` so traced-run determinism checks
           ignore them).

Normalization contract: the sequential engine's unit is one jit'd local
step over one batch (per-sample FLOPs = flops / batch); the vmap
engine's unit is the whole fused round program lowered at ``clients``
stacked participants and scan trip count 1 (per-sample =
flops / (clients * batch)). Schedule totals multiply per-sample costs by
``local_epochs`` and sum over the round plans — the same accounting as
``client_costs.schedule_costs`` — so measured and analytic columns are
directly comparable. Stochastic depth-dropout savings (FLL+DD) are an
expected-value claim the dense compiled program cannot exhibit, so both
columns here count gated layers densely; the dropout-adjusted totals
live only in the analytic full-scale table. See docs/observability.md
("Measured resources") for the documented tolerances.
"""
from __future__ import annotations

import contextlib
import os

import jax
import numpy as np

from repro.models import scan_cfg
from repro.roofline.analysis import cost_dict, memory_dict

RESOURCES_VERSION = 1

# documented measured-vs-analytic agreement bounds (per plan signature,
# reduced vit-tiny measurement config): XLA counts a handful of ops the
# analytic model folds into its 2:1 backward ratio (layernorm, softmax,
# EMA update, optimizer), so measured flops sit a few percent *above*
# analytic; buffer assignment double-books some live ranges, so measured
# peak bytes can sit well above the analytic live-set floor.
FLOPS_RTOL = 0.30          # |measured/analytic - 1| <= 0.30
MEMORY_FACTOR = 3.0        # analytic/3 <= measured peak <= 3*analytic


@contextlib.contextmanager
def unrolled_scans():
    """Fully unroll the ViT layer scans while lowering measurement
    programs. Only the lowered artifact this context produces is
    unrolled — jit executables traced outside it stay rolled, and
    ``jit.lower()`` does not populate the executable cache, so
    measurement never perturbs (or recompiles) the programs a live run
    executes."""
    prev = scan_cfg.UNROLL
    scan_cfg.UNROLL = True
    try:
        yield
    finally:
        scan_cfg.UNROLL = prev


# ---------------------------------------------------------------------------
# live device-memory watermarks
# ---------------------------------------------------------------------------
def _peak_rss_bytes() -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return 0


def device_memory_snapshot(device=None) -> dict:
    """Live memory watermark for ``device`` (default: first device).

    An accelerator reports its allocator stats via
    ``device.memory_stats()``; one that reports none is an error, never a
    host number in the device's place. Its peak is the larger of the
    buffers' peak (``peak_bytes_in_use``) and the reservations' peak
    (``peak_bytes_reserved``): on TPU a program's temporaries count only
    in the latter. The CPU backend reports none, so there the process RSS
    (``/proc/self/statm``) and its high-water mark (``VmHWM``) stand in —
    CPU arrays live on the host heap, so RSS *is* the device watermark.
    ``source`` records which path produced the numbers."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "cpu":
        stats = device.memory_stats()
        if not stats:
            raise RuntimeError(f"{device.platform} device "
                               f"{device.device_kind!r} reports no "
                               f"memory_stats()")
        in_use = int(stats.get("bytes_in_use", 0))
        peak = max(int(stats.get("peak_bytes_in_use", in_use)),
                   int(stats.get("peak_bytes_reserved", 0)))
        return {"source": "device", "bytes_in_use": in_use,
                "peak_bytes": peak}
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        return {"source": "rss", "bytes_in_use": rss,
                "peak_bytes": _peak_rss_bytes() or rss}
    except (OSError, ValueError, IndexError):
        return {"source": "none", "bytes_in_use": 0, "peak_bytes": 0}


def memory_span_attrs(device=None) -> dict:
    """``device_memory_snapshot`` as ``mem.``-prefixed span attributes.
    The ``mem.`` prefix is load-bearing: ``Tracer.structure()`` drops
    those keys so traced-vs-untraced (and traced-vs-traced) structure
    comparisons stay deterministic across machines."""
    snap = device_memory_snapshot(device)
    return {"mem.source": snap["source"],
            "mem.bytes_in_use": snap["bytes_in_use"],
            "mem.peak_bytes": snap["peak_bytes"]}


# ---------------------------------------------------------------------------
# measurement configuration
# ---------------------------------------------------------------------------
def measurement_config(arch: str = "vit-tiny", *, num_layers: int = 4,
                       batch_size: int = 8):
    """Reduced measurement shape: ``num_layers`` blocks at shrunk width
    so one CPU lowers every plan signature in seconds. Resource *ratios*
    between schedules are structural (per-block costs cancel), so they
    survive the shrink; the analytic columns are evaluated on this same
    config, which is what makes measured-vs-analytic a like-for-like
    check. Full-scale comm ratios never need this — the wire walk is
    abstract (``repro.launch.trace.emit_comm_trace``)."""
    from repro.configs.base import SSLConfig, TrainConfig, load_arch, reduced
    cfg = reduced(load_arch(arch), num_layers=num_layers,
                  num_heads=2, num_kv_heads=2)
    ssl = SSLConfig()
    train = TrainConfig(batch_size=batch_size)
    return cfg, ssl, train


def _measurement_engine(engine_name, cfg, ssl, train, fl):
    from repro.core import ssl as ssl_mod
    from repro.federated import engine as engine_mod
    from repro.federated import transport as transport_mod
    from repro.optim import make_optimizer
    bs = train.batch_size
    shard = 2 * bs
    images = np.zeros((fl.num_clients * shard, 32, 32, 3), np.float32)
    client_indices = [np.arange(i * shard, (i + 1) * shard)
                      for i in range(fl.num_clients)]
    return engine_mod.make_engine(
        engine_name, encoder=ssl_mod.make_vit_encoder(cfg), ssl_cfg=ssl,
        opt=make_optimizer(train), fl=fl, train_cfg=train, images=images,
        client_indices=client_indices,
        transport=transport_mod.Transport("fp32"))


def _plan_sig(plan):
    return (plan.sub_layers, plan.active_from, plan.align,
            plan.depth_dropout)


def stage_cost_attrs(engine, plan, *, clients: int = 1) -> dict:
    """Measured cost attributes for one stage's round program —
    ``res.``-prefixed, suitable for ``span.set(**attrs)`` on the round
    span that opens a stage. Lowering only (no compile): a few seconds
    per new stage, opt-in via ``make_obs(measure_resources=True)``."""
    with unrolled_scans():
        low = engine.lower_round(plan, clients=clients)
    cost = cost_dict(low)
    denom = engine.train_cfg.batch_size * (
        clients if engine.name == "vmap" else 1)
    flops = float(cost.get("flops", 0.0))
    return {"res.flops": flops,
            "res.flops_per_sample": flops / denom,
            "res.bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def program_memory_analytic(cfg, ssl, train, plan, engine_name: str, *,
                            clients: int = 1) -> dict:
    """Analytic estimate of the bytes the *compiled round program*
    holds — not the paper's idealized client footprint. Both engines
    keep the full state + AdamW moments resident (inputs and outputs
    are not donated), so arguments/outputs are schedule-invariant and
    only the activation live set tracks the plan; the idealized
    footprint (``client_costs.memory_bytes``) is what the paper's
    Fig. 5 prices and stays its own column. This is the prediction the
    measured ``memory_analysis`` peak is checked against
    (``MEMORY_FACTOR``)."""
    from repro.federated import comm
    from repro.roofline import client_costs as cc

    state = cc.build_ssl_param_tree(cfg, ssl)
    online_b = comm.tree_bytes(state["online"])
    state_b = comm.tree_bytes(state)
    enc_b = comm.tree_bytes(state["online"]["enc"])
    opt_b = 2 * online_b                       # AdamW m + v
    bs = train.batch_size
    batch_b = bs * 32 * 32 * 3 * 4
    c = cc.vit_costs(cfg, ssl)
    acts = (c.a_stem + (plan.sub_layers - plan.active_from) * c.a_block
            + c.a_heads) * bs * 4
    align_b = enc_b if plan.align else 0
    if engine_name == "sequential":
        args = state_b + opt_b + batch_b + align_b
        outs = state_b + opt_b
        peak = args + outs + acts
    else:
        # vmap round program: broadcast (state + server online + align
        # context) and per-client shards in; aggregated online + losses
        # out; each client's local state/opt/target copy and the wire
        # path live in temp space
        shard_b = clients * 2 * batch_b
        args = state_b + online_b + align_b + shard_b
        outs = online_b
        peak = args + outs + clients * (state_b + opt_b + acts + online_b)
    return {"argument_bytes": float(args), "output_bytes": float(outs),
            "peak_bytes": float(peak)}


# ---------------------------------------------------------------------------
# schedule measurement
# ---------------------------------------------------------------------------
def measure_schedule(schedule: str, engine_name: str, *, cfg=None, ssl=None,
                     train=None, rounds: int = 20, local_epochs: int = 3,
                     depth_dropout: float = 0.5, compile_memory: bool = True,
                     clients: int = 1, log=None) -> dict:
    """Measure one schedule on one engine at the measurement config.

    Lowers each *distinct* plan signature once for FLOPs; compiles only
    the signature the analytic model predicts as the schedule's memory
    peak (``compile_memory=False`` skips the compile and reports
    analytic-only memory). Returns measured and analytic columns side by
    side — totals use the ``schedule_costs`` accounting (per-sample x
    ``local_epochs``, summed over round plans; dense, see module
    docstring for the FLL+DD convention)."""
    from repro.configs.base import FLConfig
    from repro.core import schedule as sched
    from repro.federated import comm
    from repro.roofline import client_costs as cc

    if cfg is None or ssl is None or train is None:
        mcfg, mssl, mtrain = measurement_config()
        cfg, ssl, train = cfg or mcfg, ssl or mssl, train or mtrain
    fl = FLConfig(rounds=rounds, schedule=schedule, num_clients=2,
                  local_epochs=local_epochs, depth_dropout=depth_dropout)
    plans = sched.build_schedule(fl, cfg.num_layers)
    eng = _measurement_engine(engine_name, cfg, ssl, train, fl)

    costs = cc.vit_costs(cfg, ssl)
    params_bytes = comm.tree_bytes(
        cc.build_ssl_param_tree(cfg, ssl)["online"]["enc"])
    bs = train.batch_size
    denom = bs * (clients if engine_name == "vmap" else 1)

    sigs = {}
    for p in plans:
        sigs.setdefault(_plan_sig(p), p)
    stages, lowered = [], {}
    for sig, p in sigs.items():
        if log:
            log(f"[resources] lower {schedule}/{engine_name} "
                f"sub={p.sub_layers} act={p.active_from}")
        with unrolled_scans():
            low = eng.lower_round(p, clients=clients)
        lowered[sig] = low
        flops = float(cost_dict(low).get("flops", 0.0))
        stages.append({
            "sub_layers": p.sub_layers, "active_from": p.active_from,
            "align": bool(p.align), "depth_dropout": float(p.depth_dropout),
            "rounds": sum(1 for q in plans if _plan_sig(q) == sig),
            "flops_per_sample": flops / denom,
            "analytic_flops_per_sample":
                float(cc.flops_per_sample_round(costs, p)),
            "analytic_memory_bytes":
                float(cc.memory_bytes(costs, p, bs, params_bytes)),
        })

    peak_i = max(range(len(stages)),
                 key=lambda i: stages[i]["analytic_memory_bytes"])
    mem = None
    if compile_memory:
        peak_sig, peak_plan = list(sigs.items())[peak_i]
        if log:
            log(f"[resources] compile peak sig {schedule}/{engine_name} "
                f"sub={peak_sig[0]} act={peak_sig[1]}")
        # memory is measured on the ROLLED program — the artifact we
        # actually run. The unrolled lowering exists only for flops:
        # its buffer assignment keeps every unrolled layer's
        # activations live at once and inflates temp bytes by ~the
        # layer count.
        mem = memory_dict(eng.lower_round(peak_plan, clients=clients)
                          .compile())

    flops_total = sum(s["flops_per_sample"] * s["rounds"] * local_epochs
                      for s in stages)
    analytic_total = sum(
        s["analytic_flops_per_sample"] * s["rounds"] * local_epochs
        for s in stages)
    peak_plan = list(sigs.values())[peak_i]
    out = {
        "schedule": schedule, "engine": engine_name,
        "num_layers": cfg.num_layers, "batch_size": bs,
        "rounds": rounds, "local_epochs": local_epochs,
        "clients": clients,
        "stages": stages,
        "flops_total": flops_total,
        "analytic_flops_total": analytic_total,
        "analytic_peak_memory": stages[peak_i]["analytic_memory_bytes"],
        "program_peak_analytic": program_memory_analytic(
            cfg, ssl, train, peak_plan, engine_name,
            clients=clients)["peak_bytes"],
        "peak_memory": None, "argument_bytes": None,
        "output_bytes": None, "temp_bytes": None,
    }
    if mem is not None:
        out.update(peak_memory=float(mem["peak_bytes"]),
                   argument_bytes=float(mem["argument_bytes"]),
                   output_bytes=float(mem["output_bytes"]),
                   temp_bytes=float(mem["temp_bytes"]))
    return out
