"""Benchmark driver — one benchmark per paper table/figure.

  table1   FedMoCo vs FedMoCo-LW resources (paper Table 1)
  table2   per-stage exchange characteristics (paper Table 2)
  table3   cost multipliers, all methods (paper Table 3 cost columns)
  table4   auxiliary-data amount (paper Table 4, reduced-scale FL)
  fig5     per-round memory / FLOPs / download / upload curves
  fig6     batch size vs peak memory
  fig14    rounds-per-stage allocation -> effective rounds per layer
  kernels  Pallas kernels vs jnp oracle (allclose + timing)
  roofline dry-run roofline table (reads results/dryrun_*.json)
  engine   sequential vs vmap round engine throughput
  transport wire payload pack/unpack throughput + per-codec compression
           per schedule (writes results/transport_bench.json)
  simulation heterogeneous-fleet round policies: wall-clock to target
           loss, device-seconds, energy, drops per schedule x fleet x
           policy (writes results/simulation_bench.json)
  privacy  DP-FedAvg + secure aggregation: utility delta, (eps, delta),
           wire/mask overhead and rounds/sec per schedule x codec x
           privacy mode (writes results/privacy_bench.json)
  resources measured FLOPs/memory from the compiled XLA round programs
           vs the analytic roofline vs the paper's Table 3 multipliers,
           per engine x schedule (writes results/resources_bench.json)

``python -m benchmarks.run`` runs the fast set (``--only`` takes a
comma-separated subset); ``--full`` adds the reduced-scale FL accuracy
benchmarks (table4), which train for real. Every written document
carries the shared provenance header (``benchmarks.provenance``) and is
validated against ``benchmarks.schemas`` before it hits disk;
``benchmarks.compare`` diffs results against the committed baselines
under ``benchmarks/baselines/``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np                                        # noqa: E402

from benchmarks import resources                          # noqa: E402
from benchmarks.provenance import provenance              # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs import NOOP_OBS                            # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results"

# ``--trace`` swaps this for an enabled bundle; benches that run real FL
# rounds pass it into run_fedssl so the bench trace shows the full span
# tree (docs/observability.md).
OBS = NOOP_OBS

# schedule names / paper Table 3 cost multipliers — single definitions
# in repro.core.schedule and repro.roofline.client_costs
from repro.core.schedule import SCHEDULES                 # noqa: E402

NAMES = resources.SCHEDULE_NAMES
PAPER_MULT = resources.PAPER_MULT


def bench_table1():
    print("\n== Table 1: FedMoCo (e2e) vs FedMoCo-LW (layer-wise), "
          "per client ==")
    rows = {}
    for s in ("e2e", "layerwise"):
        rows[s] = resources.schedule_costs(s)
    print(f"{'':14s} {'Memory(MB)':>12s} {'FLOPs(x1e10)':>14s} "
          f"{'Comm(MB)':>10s}")
    for s, r in rows.items():
        print(f"{NAMES[s]:14s} {r['peak_memory'] / 1e6:12.0f} "
              f"{r['flops_total'] / 1e10:14.2f} "
              f"{r['comm_total'] / 1e6:10.0f}")
    m = rows["e2e"]["peak_memory"] / rows["layerwise"]["peak_memory"]
    f = rows["e2e"]["flops_total"] / rows["layerwise"]["flops_total"]
    c = rows["e2e"]["comm_total"] / rows["layerwise"]["comm_total"]
    print(f"reduction LW vs e2e: memory {m:.1f}x  flops {f:.1f}x  "
          f"comm {c:.1f}x   (paper Table 1: 4.0x, 2.9x, 12x)")
    return rows


def bench_table2():
    print("\n== Table 2: characteristics at stage s ==")
    from repro.configs.base import FLConfig
    from repro.core import schedule as sched
    print(f"{'method':12s} {'active':16s} {'frozen':14s} "
          f"{'download':12s} {'upload':10s} {'calib':6s}")
    for s in SCHEDULES:
        plans = sched.build_schedule(FLConfig(rounds=24, schedule=s), 12)
        p = plans[12]                       # a mid-training round

        def rng_(t):
            lo, hi = t
            return f"L{lo + 1}..L{hi}" if hi - lo > 1 else f"L{hi}"
        active = (f"L{p.active_from + 1}..L{p.sub_layers}"
                  if p.sub_layers - p.active_from > 1
                  else f"L{p.sub_layers}")
        frozen = f"L1..L{p.active_from}" if p.active_from else "-"
        print(f"{NAMES[s]:12s} {active:16s} {frozen:14s} "
              f"{rng_(p.download_stages):12s} {rng_(p.upload_stages):10s} "
              f"{'yes' if p.server_calibrate else 'no':6s}")


def bench_table3():
    print("\n== Table 3 (cost columns): multipliers vs FedMoCo ==")
    base = resources.schedule_costs("e2e")
    print(f"{'method':12s} {'Memory':>8s} {'FLOPs':>8s} {'Comm':>8s} "
          f"{'paper(M,F,C)':>20s}")
    out = {}
    for s in SCHEDULES:
        r = resources.schedule_costs(s)
        m = r["peak_memory"] / base["peak_memory"]
        f = r["flops_total"] / base["flops_total"]
        c = r["comm_total"] / base["comm_total"]
        pm, pf, pc = PAPER_MULT[s]
        print(f"{NAMES[s]:12s} {m:8.2f} {f:8.2f} {c:8.2f} "
              f"{f'{pm:.2f},{pf:.2f},{pc:.2f}':>20s}")
        out[s] = (m, f, c)
    return out


def bench_fig5():
    print("\n== Fig. 5: per-round curves (values at stages 1, 6, 12) ==")
    for s in SCHEDULES:
        r = resources.schedule_costs(s)
        ser = r["series"]
        idx = [0, len(ser["memory"]) // 2, -1]
        mem = [f"{ser['memory'][i] / 1e6:.0f}" for i in idx]
        dwn = [f"{ser['download'][i] / 1e6:.2f}" for i in idx]
        upl = [f"{ser['upload'][i] / 1e6:.2f}" for i in idx]
        print(f"{NAMES[s]:12s} memMB {mem}  downMB {dwn}  upMB {upl}")


def bench_fig6():
    print("\n== Fig. 6b: peak memory vs batch size ==")
    print(f"{'batch':>6s}" + "".join(f"{NAMES[s]:>14s}" for s in SCHEDULES))
    for b in (64, 128, 256, 512, 1024):
        row = [f"{b:6d}"]
        for s in SCHEDULES:
            r = resources.schedule_costs(s, batch=b)
            row.append(f"{r['peak_memory'] / 1e6:14.0f}")
        print("".join(row))


def bench_fig14():
    print("\n== Fig. 13/14: rounds-per-stage allocations ==")
    from repro.core.schedule import stage_rounds
    for alloc in ("uniform", "right_skewed", "left_skewed"):
        rs = stage_rounds(180, 12, alloc)
        # effective rounds layer L trains: layerwise -> its stage's rounds;
        # progressive -> sum of rounds from its stage onward
        prog = [sum(rs[i:]) for i in range(12)]
        print(f"{alloc:14s} per-stage {rs}")
        print(f"{'':14s} progressive effective {prog}")


def bench_kernels():
    print("\n== Pallas kernels vs oracle (interpret mode, CPU) ==")
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    rows = []
    q = jax.random.normal(key, (2, 256, 4, 64))
    k = jax.random.normal(key, (2, 256, 2, 64))
    v = jax.random.normal(key, (2, 256, 2, 64))
    t0 = time.perf_counter()
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    t_k = time.perf_counter() - t0
    want = ref.sdpa_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(out - want)))
    rows.append(("flash_attention", t_k, err))
    xh = jax.random.normal(key, (2, 256, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (2, 256, 4)))
    a = -dt * 0.1
    Bm = jax.random.normal(key, (2, 256, 64))
    Cm = jax.random.normal(key, (2, 256, 64))
    t0 = time.perf_counter()
    out = ops.ssd_scan(xh, dt, a, Bm, Cm, interpret=True)
    rows.append(("mamba2_ssd_scan", time.perf_counter() - t0,
                 float(jnp.max(jnp.abs(
                     out - ref.ssd_scan_ref(xh, dt, a, Bm, Cm))))))
    qq = jax.random.normal(key, (256, 128))
    kk = jax.random.normal(key, (256, 128))
    t0 = time.perf_counter()
    got = ops.fused_info_nce(qq, kk, 0.2, interpret=True)
    from repro.core.losses import info_nce
    rows.append(("fused_info_nce", time.perf_counter() - t0,
                 abs(float(got) - float(info_nce(qq, kk, 0.2)))))
    x = jax.random.normal(key, (1024, 256))
    s = jnp.ones((256,))
    t0 = time.perf_counter()
    got = ops.fused_rmsnorm(x, s, interpret=True)
    rows.append(("fused_rmsnorm", time.perf_counter() - t0,
                 float(jnp.max(jnp.abs(got - ref.rmsnorm_ref(x, s))))))
    for name, dt_, err in rows:
        print(f"{name:20s} first-call {dt_ * 1e3:8.1f}ms  maxerr {err:.2e}")
        assert err < 5e-3
    print("(interpret mode validates semantics; TPU timing is the "
          "dry-run/roofline's job)")


def bench_roofline():
    print("\n== Roofline table (from dry-run results) ==")
    found = sorted(RESULTS.glob("dryrun_*.json"))
    if not found:
        print("  (no results/dryrun_*.json yet — run "
              "python -m repro.launch.dryrun --out "
              "results/dryrun_16x16.json)")
        return
    for f in found:
        rows = json.loads(f.read_text())
        print(f"-- {f.name}: {len(rows)} rows")
        for r in rows:
            print(f"  {r['arch']:28s} {r['shape']:12s} {r['mode']:9s} "
                  f"comp {r['compute_s'] * 1e3:9.2f}ms "
                  f"mem {r['memory_s'] * 1e3:9.2f}ms "
                  f"coll {r['collective_s'] * 1e3:9.2f}ms "
                  f"-> {r['dominant']:10s} useful "
                  f"{r['useful_ratio'] * 100:5.1f}%")


def bench_engine(rounds=8, clients=8):
    """Sequential vs vmap engine throughput, 8 clients/round.

    Uses the regime the vectorized engine exists for — many clients with
    small local datasets (one local step each, as in FedSGD-style rounds) —
    where the sequential simulator's per-client dispatch overhead dominates
    wall-clock. Steady-state rounds/sec excludes round 1, which pays the
    one-time XLA compile in both engines.
    """
    print(f"\n== Engine: sequential vs vmap rounds/sec "
          f"({clients} clients/round) ==")
    import jax
    import jax.numpy as jnp
    from repro.configs.base import (FLConfig, ModelConfig, SSLConfig,
                                    TrainConfig)
    from repro.data import iid_partition, synthetic_images
    from repro.federated.driver import run_fedssl
    cfg = ModelConfig("t-vit", "dense", 2, 32, 2, 2, 64, 0, causal=False,
                      compute_dtype="float32", act="gelu")
    sslc = SSLConfig(proj_hidden=32, pred_hidden=32, proj_dim=16)
    tc = TrainConfig(batch_size=8, base_lr=1.5e-4)
    samples = clients * tc.batch_size
    key = jax.random.PRNGKey(0)
    imgs, _ = synthetic_images(key, samples, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(samples, clients)]
    fl = FLConfig(num_clients=clients, rounds=rounds, local_epochs=1,
                  schedule="e2e")
    rps = {}
    for engine in ("sequential", "vmap"):
        times = [time.perf_counter()]
        _, hist = run_fedssl(cfg, sslc, fl, tc, images=imgs,
                             client_indices=idx, key=key, engine=engine,
                             log=lambda m: times.append(time.perf_counter()),
                             obs=OBS)
        total = times[-1] - times[0]
        rps[engine] = (rounds - 1) / (times[-1] - times[1])
        print(f"{engine:12s} {total:6.1f}s total (incl. compile)  "
              f"steady-state {rps[engine]:6.2f} rounds/s  "
              f"final loss {hist.loss[-1]:.4f}")
    print(f"vmap speedup over sequential: "
          f"{rps['vmap'] / rps['sequential']:.2f}x rounds/sec")
    return rps


def bench_transport(reps=5, codec_reps=3):
    """Wire transport, xla vs pallas engines: pack/unpack throughput per
    schedule (mid-training round, full-size ViT-T + MoCo heads), per-codec
    compression ratios, and codec encode/decode throughput on the largest
    (e2e) payload. Validates against ``benchmarks.schemas``, emits one
    BENCH json line and writes results/transport_bench.json for the CI
    artifact.

    Codec throughput uses ``codec_reps`` (the jit'd XLA top-k encode runs
    seconds per call on a 26M-element payload; best-of-3 keeps the bench
    under a minute without changing the min-statistics convention)."""
    print("\n== Transport: pack/unpack + codecs, xla vs pallas ==")
    import jax
    from benchmarks.schemas import validate_transport_bench
    from benchmarks.timing import bench_seconds, gbps
    from repro.configs.base import FLConfig, SSLConfig, load_arch
    from repro.core import schedule as sched
    from repro.core import ssl as ssl_mod
    from repro.federated import comm
    from repro.federated.transport import (Transport, kernel_codec_fns,
                                           kernel_pack, kernel_unpack,
                                           make_codec, pack_stage_payload,
                                           unpack_stage_payload)

    cfg = load_arch("vit-tiny")
    sslc = SSLConfig()
    enc = ssl_mod.make_vit_encoder(cfg)
    online = ssl_mod.ssl_init(jax.random.PRNGKey(0), enc, sslc)["online"]
    codecs = ("fp32", "fp16", "bf16", "int8", "topk:0.1")
    rows = []
    e2e_spec = None
    for schedule in SCHEDULES:
        plans = sched.build_schedule(FLConfig(rounds=24, schedule=schedule),
                                     cfg.num_layers)
        plan = plans[len(plans) // 2]
        t0s = Transport("fp32")
        spec = t0s.plan_specs(online, plan)["upload"]
        if schedule == "e2e":
            e2e_spec = spec
        nbytes = spec.payload_bytes
        xpack = jax.jit(lambda p: pack_stage_payload(p, spec))
        xunpack = jax.jit(lambda b, f: unpack_stage_payload(b, f, spec))
        flat_x = jax.block_until_ready(xpack(online))
        flat_h = kernel_pack(online, spec)
        pack_s = {"xla": bench_seconds(xpack, online, reps=reps),
                  "pallas": bench_seconds(
                      lambda: kernel_pack(online, spec), reps=reps)}
        unpack_s = {"xla": bench_seconds(xunpack, online, flat_x,
                                         reps=reps),
                    "pallas": bench_seconds(
                        lambda: kernel_unpack(online, flat_h, spec),
                        reps=reps)}
        mb = nbytes / 1e6
        # throughput figures cover the upload payload; per-codec wire_mb /
        # ratio below cover the full round trip (download + upload)
        row = {"schedule": schedule, "upload_payload_mb": round(mb, 3),
               "pack_gbps": {e: round(gbps(nbytes, s), 3)
                             for e, s in pack_s.items()},
               "unpack_gbps": {e: round(gbps(nbytes, s), 3)
                               for e, s in unpack_s.items()},
               "pack_speedup": round(pack_s["xla"] / pack_s["pallas"], 2),
               "unpack_speedup": round(
                   unpack_s["xla"] / unpack_s["pallas"], 2),
               "codecs": {}}
        analytic = comm.round_comm_bytes(online, plan)
        for name in codecs:
            t = Transport(name)
            sp = t.plan_specs(online, plan)
            wire = {d: t.wire_bytes(sp[d]) for d in ("download", "upload")}
            ratio = ((sp["download"].payload_bytes
                      + sp["upload"].payload_bytes)
                     / max(1, wire["download"] + wire["upload"]))
            row["codecs"][name] = {
                "round_wire_mb": round(
                    (wire["download"] + wire["upload"]) / 1e6, 4),
                "ratio": round(ratio, 2)}
            if name == "fp32":
                assert wire == analytic, (wire, analytic)
        rows.append(row)
        print(f"{NAMES[schedule]:12s} payload {mb:7.2f}MB  "
              f"pack {row['pack_gbps']['xla']:6.2f} -> "
              f"{row['pack_gbps']['pallas']:6.2f} GB/s "
              f"({row['pack_speedup']:.1f}x)  "
              f"unpack {row['unpack_gbps']['xla']:6.2f} -> "
              f"{row['unpack_gbps']['pallas']:6.2f} GB/s "
              f"({row['unpack_speedup']:.1f}x)")

    # codec encode/decode throughput, timed once on the largest payload
    codec_rows = []
    nbytes = e2e_spec.payload_bytes
    flat_x = jax.block_until_ready(
        jax.jit(lambda p: pack_stage_payload(p, e2e_spec))(online))
    flat_h = kernel_pack(online, e2e_spec)
    for name in codecs:
        codec = make_codec(name)
        xenc = jax.jit(lambda f: codec.encode(f, e2e_spec))
        xdec = jax.jit(lambda w: codec.decode(w, e2e_spec))
        kenc, kdec = kernel_codec_fns(codec, e2e_spec)
        wire_x = jax.block_until_ready(xenc(flat_x))
        wire_h = kenc(flat_h)
        enc_s = {"xla": bench_seconds(xenc, flat_x, reps=codec_reps,
                                      warmup=1),
                 "pallas": bench_seconds(kenc, flat_h, reps=codec_reps,
                                         warmup=1)}
        dec_s = {"xla": bench_seconds(xdec, wire_x, reps=codec_reps,
                                      warmup=1),
                 "pallas": bench_seconds(kdec, wire_h, reps=codec_reps,
                                         warmup=1)}
        crow = {"codec": name, "payload_mb": round(nbytes / 1e6, 3),
                "encode_gbps": {e: round(gbps(nbytes, s), 3)
                                for e, s in enc_s.items()},
                "decode_gbps": {e: round(gbps(nbytes, s), 3)
                                for e, s in dec_s.items()}}
        codec_rows.append(crow)
        print(f"codec {name:9s} enc {crow['encode_gbps']['xla']:8.2f} -> "
              f"{crow['encode_gbps']['pallas']:8.2f} GB/s   "
              f"dec {crow['decode_gbps']['xla']:8.2f} -> "
              f"{crow['decode_gbps']['pallas']:8.2f} GB/s")

    doc = {"bench": "transport",
           "config": {"arch": "vit-tiny", "reps": reps,
                      "codec_reps": codec_reps, "codecs": list(codecs),
                      "engines": ["xla", "pallas"],
                      "schedules": list(SCHEDULES)},
           "rows": rows, "codec_rows": codec_rows,
           "provenance": provenance()}
    errors = validate_transport_bench(doc)
    assert not errors, errors
    RESULTS.mkdir(exist_ok=True)
    out = RESULTS / "transport_bench.json"
    out.write_text(json.dumps(doc, indent=1))
    print("BENCH " + json.dumps({"bench": "transport", "rows": len(rows),
                                 "codec_rows": len(codec_rows)}))
    print(f"(schema-validated; fp32 wire bytes == analytic comm bytes "
          f"verified; json -> {out})")
    return doc


def bench_simulation(rounds=6, clients=6, clients_per_round=4,
                     schedules=("e2e", "lw_fedssl"), fleets=None,
                     policies=None, seed=0, write=True):
    """Fleet simulation: schedules x fleet profiles x round policies.

    For each (schedule, fleet) group the first policy's best round-mean
    loss becomes the group's target, and every policy reports the
    simulated wall-clock needed to reach it — alongside device-seconds,
    the energy proxy and dropped client-rounds. Writes
    results/simulation_bench.json (validated against
    benchmarks.schemas) and emits one BENCH json line. Tests call this
    with smaller knobs and ``write=False``.
    """
    print("\n== Simulation: fleet x round-policy cost frontier ==")
    import jax
    import jax.numpy as jnp
    from repro.configs.base import (FLConfig, ModelConfig, SSLConfig,
                                    TrainConfig)
    from repro.data import iid_partition, synthetic_images
    from repro.federated import fleet as fleet_mod
    from repro.federated import simulation as sim_mod
    from repro.federated.driver import run_fedssl
    from benchmarks.schemas import validate_simulation_bench

    fleets = tuple(fleets or fleet_mod.PROFILES)
    policies = tuple(policies or sim_mod.POLICIES)
    cfg = ModelConfig("t-vit", "dense", 2, 32, 2, 2, 64, 0, causal=False,
                      compute_dtype="float32", act="gelu")
    sslc = SSLConfig(proj_hidden=32, pred_hidden=32, proj_dim=16)
    tc = TrainConfig(batch_size=8, base_lr=1.5e-4)
    samples = clients * 2 * tc.batch_size
    imgs, _ = synthetic_images(jax.random.PRNGKey(seed), samples, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(samples, clients)]
    rows = []
    for schedule in schedules:
        fl = FLConfig(num_clients=clients, rounds=rounds, local_epochs=1,
                      clients_per_round=clients_per_round,
                      schedule=schedule)
        for prof in fleets:
            target = None
            for policy in policies:
                sim = sim_mod.make_sim(
                    fleet_mod.make_fleet(prof, clients, seed=seed),
                    policy, num_clients=clients, seed=seed)
                _, hist = run_fedssl(cfg, sslc, fl, tc, images=imgs,
                                     client_indices=idx,
                                     key=jax.random.PRNGKey(seed), sim=sim,
                                     obs=OBS)
                if target is None:     # first policy sets the group bar
                    target = min(hist.loss)
                ttt = hist.wall_clock_to_loss(target)
                rows.append({
                    # the full versioned round series rides along so the
                    # bench json round-trips through FLHistory.from_dict
                    "history": hist.to_dict(),
                    "schedule": schedule, "fleet": prof, "policy": policy,
                    "rounds": rounds, "clients": clients,
                    "clients_per_round": clients_per_round,
                    "target_loss": round(float(target), 6),
                    "final_loss": round(float(hist.loss[-1]), 6),
                    "wall_clock_to_target_s":
                        None if ttt is None else round(float(ttt), 6),
                    "total_wall_clock_s":
                        round(float(hist.total_wall_clock), 6),
                    "device_seconds":
                        round(float(hist.total_device_seconds), 6),
                    "energy_j": round(float(hist.total_energy), 6),
                    "dropped_client_rounds": int(hist.total_dropped),
                })
                r = rows[-1]
                tt = (f"{r['wall_clock_to_target_s']:.2f}s"
                      if r["wall_clock_to_target_s"] is not None
                      else "  -  ")
                print(f"{schedule:10s} {prof:18s} {policy:14s} "
                      f"to-target {tt:>8s}  wall "
                      f"{r['total_wall_clock_s']:7.2f}s  dev "
                      f"{r['device_seconds']:7.2f}s  "
                      f"{r['energy_j']:6.2f}J  "
                      f"dropped {r['dropped_client_rounds']}")
    doc = {"bench": "simulation",
           "config": {"rounds": rounds, "clients": clients,
                      "clients_per_round": clients_per_round,
                      "seed": seed, "schedules": list(schedules),
                      "fleets": list(fleets), "policies": list(policies),
                      "engine": "sequential"},
           "rows": rows, "provenance": provenance(seed=seed)}
    errors = validate_simulation_bench(doc)
    assert not errors, errors
    if write:
        RESULTS.mkdir(exist_ok=True)
        out = RESULTS / "simulation_bench.json"
        out.write_text(json.dumps(doc, indent=1))
        print("BENCH " + json.dumps({"bench": "simulation",
                                     "rows": len(rows)}))
        print(f"(schema-validated; json -> {out})")
    return doc


def bench_privacy(rounds=4, clients=4, schedules=("e2e", "lw_fedssl"),
                  codecs=("fp32", "int8", "topk:0.25"), seed=0, write=True):
    """Privacy: codec x schedule x (DP, secure-agg) cost frontier.

    For every schedule x codec cell, four runs — baseline, client-level
    DP (clip=1, z=1.1), pairwise-mask secure aggregation, and both —
    reporting utility delta vs the cell's baseline, the (eps, delta)
    spent, measured wire MB plus the secure-agg mask overhead, and the
    steady-state rounds/sec cost. Writes results/privacy_bench.json
    (validated against benchmarks.schemas) and emits one BENCH json
    line. Tests call this with smaller knobs and ``write=False``.
    """
    print("\n== Privacy: DP / secure-agg utility + overhead frontier ==")
    import jax
    import jax.numpy as jnp
    from benchmarks.schemas import validate_privacy_bench
    from repro.configs.base import (FLConfig, ModelConfig, SSLConfig,
                                    TrainConfig)
    from repro.data import iid_partition, synthetic_images
    from repro.federated.driver import run_fedssl
    from repro.privacy import PrivacyConfig

    cfg = ModelConfig("t-vit", "dense", 2, 32, 2, 2, 64, 0, causal=False,
                      compute_dtype="float32", act="gelu")
    sslc = SSLConfig(proj_hidden=32, pred_hidden=32, proj_dim=16)
    tc = TrainConfig(batch_size=8, base_lr=1.5e-4)
    samples = clients * 2 * tc.batch_size
    imgs, _ = synthetic_images(jax.random.PRNGKey(seed), samples, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(samples, clients)]
    modes = (("baseline", None),
             ("dp", PrivacyConfig(clip=1.0, noise_multiplier=1.1)),
             ("secure", PrivacyConfig(secure_agg=True)),
             ("dp+secure", PrivacyConfig(clip=1.0, noise_multiplier=1.1,
                                         secure_agg=True)))
    rows = []
    for schedule in schedules:
        fl = FLConfig(num_clients=clients, rounds=rounds, local_epochs=1,
                      schedule=schedule)
        for codec in codecs:
            base_loss = base_rps = None
            for mode, privacy in modes:
                times = [time.perf_counter()]
                _, hist = run_fedssl(
                    cfg, sslc, fl, tc, images=imgs, client_indices=idx,
                    key=jax.random.PRNGKey(seed), codec=codec,
                    privacy=privacy, obs=OBS,
                    log=lambda m: times.append(time.perf_counter()))
                # steady-state rounds/sec: round 1 pays the XLA compile
                rps = (rounds - 1) / max(times[-1] - times[1], 1e-9)
                if mode == "baseline":
                    base_loss, base_rps = hist.loss[-1], rps
                dp = privacy is not None and privacy.clip > 0.0
                rows.append({
                    "schedule": schedule, "codec": codec, "dp": dp,
                    "secure_agg": bool(privacy is not None
                                       and privacy.secure_agg),
                    "rounds": rounds, "clients": clients,
                    "final_loss": round(float(hist.loss[-1]), 6),
                    "utility_delta": round(
                        float(hist.loss[-1] - base_loss), 6),
                    "epsilon": (round(float(hist.epsilon[-1]), 6)
                                if dp else None),
                    "clip_fraction": (round(float(
                        np.mean(hist.clip_fraction)), 6) if dp else None),
                    "wire_mb": round(float(hist.total_wire) / 1e6, 4),
                    "mask_overhead_mb": round(float(
                        sum(hist.secure_agg_overhead_bytes)) / 1e6, 4),
                    "rounds_per_sec": round(rps, 4),
                    "slowdown": round(base_rps / max(rps, 1e-9), 3),
                })
                r = rows[-1]
                eps = (f"eps {r['epsilon']:7.2f}" if r["epsilon"]
                       is not None else "eps    -  ")
                print(f"{schedule:10s} {codec:10s} {mode:10s} "
                      f"loss {r['final_loss']:7.4f} "
                      f"(d {r['utility_delta']:+8.4f})  {eps}  "
                      f"wire {r['wire_mb']:6.2f}MB "
                      f"+mask {r['mask_overhead_mb']:5.2f}MB  "
                      f"{r['rounds_per_sec']:5.2f} r/s "
                      f"({r['slowdown']:.2f}x)")
    doc = {"bench": "privacy",
           "config": {"rounds": rounds, "clients": clients, "seed": seed,
                      "schedules": list(schedules), "codecs": list(codecs),
                      "modes": [m for m, _ in modes],
                      "dp_clip": 1.0, "dp_noise_multiplier": 1.1,
                      "dp_delta": 1e-5, "engine": "sequential"},
           "rows": rows, "provenance": provenance(seed=seed)}
    errors = validate_privacy_bench(doc)
    assert not errors, errors
    if write:
        RESULTS.mkdir(exist_ok=True)
        out = RESULTS / "privacy_bench.json"
        out.write_text(json.dumps(doc, indent=1))
        print("BENCH " + json.dumps({"bench": "privacy",
                                     "rows": len(rows)}))
        print(f"(schema-validated; json -> {out})")
    return doc


def bench_resources(engines=("sequential", "vmap"), measure_rounds=20,
                    compile_memory=True, seed=0, write=True):
    """Measured resources: XLA cost/memory analysis vs the analytic
    roofline, per engine x schedule.

    This is the old standalone analytic table folded into a bench suite:
    each row carries the analytic columns (``repro.roofline.client_costs``)
    next to the *measured* ones — FLOPs from ``Lowered.cost_analysis()``
    on the unrolled round programs, peak/argument/output memory from the
    compiled rolled program of each schedule's peak stage, and full-scale
    comm from the abstract transport walk (which reproduces the paper's
    0.08 / 0.31 / 0.54 comm column exactly). Writes
    results/resources_bench.json (validated against benchmarks.schemas,
    whose validator also enforces the measured-vs-analytic tolerances)
    and emits one BENCH json line. Tests call this with smaller knobs and
    ``write=False``; CI's regression job diffs the written document
    against benchmarks/baselines/ via benchmarks.compare.
    """
    print("\n== Resources: measured (XLA) vs analytic vs paper ==")
    from benchmarks.schemas import validate_resources_bench
    from repro.launch.trace import paper_table, print_paper_table

    table = paper_table(engines=tuple(engines),
                        measure_rounds=measure_rounds,
                        compile_memory=compile_memory,
                        log=print)
    print_paper_table(table)
    rows = table.pop("rows")
    doc = {"bench": "resources", "config": table, "rows": rows,
           "provenance": provenance(seed=seed)}
    errors = validate_resources_bench(doc)
    assert not errors, errors
    if write:
        RESULTS.mkdir(exist_ok=True)
        out = RESULTS / "resources_bench.json"
        out.write_text(json.dumps(doc, indent=1))
        print("BENCH " + json.dumps({"bench": "resources",
                                     "rows": len(rows)}))
        print(f"(schema-validated incl. measured-vs-analytic tolerances; "
              f"json -> {out})")
    return doc


def bench_table4(rounds=4):
    print("\n== Table 4: auxiliary data amount (reduced-scale, "
          "synthetic) ==")
    import jax
    import jax.numpy as jnp
    from repro.configs.base import (FLConfig, ModelConfig, SSLConfig,
                                    TrainConfig)
    from repro.core import ssl as ssl_mod
    from repro.data import iid_partition, synthetic_images
    from repro.federated import eval as fl_eval
    from repro.federated.driver import run_fedssl
    cfg = ModelConfig("t-vit", "dense", 4, 48, 4, 4, 96, 0, causal=False,
                      compute_dtype="float32", act="gelu")
    sslc = SSLConfig(proj_hidden=96, pred_hidden=96, proj_dim=24)
    tc = TrainConfig(batch_size=32, base_lr=1.5e-4)
    key = jax.random.PRNGKey(0)
    imgs, labels = synthetic_images(key, 512, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(512, 2)]
    enc = ssl_mod.make_vit_encoder(cfg)
    for frac in (0.05, 0.25, 1.0):
        aux = imgs[: int(512 * frac)]
        fl = FLConfig(num_clients=2, rounds=rounds, local_epochs=1,
                      schedule="lw_fedssl", server_epochs=1)
        state, hist = run_fedssl(cfg, sslc, fl, tc, images=imgs,
                                 client_indices=idx, aux_images=aux, key=key)
        acc = fl_eval.linear_eval(enc, state["online"]["enc"],
                                  imgs[:256], labels[:256], imgs[256:],
                                  labels[256:], num_classes=10, epochs=3,
                                  batch_size=64)
        print(f"aux fraction {frac:5.2f}: final loss {hist.loss[-1]:.3f} "
              f"linear acc {acc * 100:.1f}%")


BENCHES = {
    "table1": bench_table1, "table2": bench_table2, "table3": bench_table3,
    "fig5": bench_fig5, "fig6": bench_fig6, "fig14": bench_fig14,
    "kernels": bench_kernels, "roofline": bench_roofline,
    "engine": bench_engine, "transport": bench_transport,
    "simulation": bench_simulation, "privacy": bench_privacy,
    "resources": bench_resources,
}
FULL_BENCHES = {"table4": bench_table4}


def _select_benches(only: str, benches: dict) -> dict:
    """``--only`` value (comma-separated bench names) -> ordered subset
    of ``benches``; raises ValueError on unknown or empty names so CI
    fails loudly instead of silently running nothing."""
    names = [n.strip() for n in only.split(",") if n.strip()]
    if not names:
        raise ValueError("--only: no bench names given")
    unknown = [n for n in names if n not in benches]
    if unknown:
        raise ValueError(
            f"--only: unknown bench(es) {', '.join(unknown)}; "
            f"available: {', '.join(sorted(benches))}")
    return {n: benches[n] for n in names}


def main():
    global OBS
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only these benches (comma-separated, e.g. "
                         "--only transport,privacy)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--trace", action="store_true",
                    help="span-trace the bench run (one span per bench, "
                         "full FL span trees inside) and write "
                         "results/bench_trace.jsonl + .chrome.json")
    args = ap.parse_args()
    if args.trace:
        from repro.obs import make_obs
        OBS = make_obs(trace=True, source="benchmarks.run")
    todo = dict(BENCHES)
    if args.full:
        todo.update(FULL_BENCHES)
    if args.only:
        try:
            todo = _select_benches(args.only, {**BENCHES, **FULL_BENCHES})
        except ValueError as e:
            ap.error(str(e))
    t0 = time.perf_counter()
    for name, fn in todo.items():
        with OBS.tracer.span(f"bench.{name}", cat="bench"):
            fn()
    print(f"\nall benchmarks done in {time.perf_counter() - t0:.1f}s")
    if args.trace:
        RESULTS.mkdir(exist_ok=True)
        written = OBS.export(
            trace_jsonl=RESULTS / "bench_trace.jsonl",
            chrome_trace=RESULTS / "bench_trace.chrome.json",
            benches=sorted(todo))
        for kind, path in sorted(written.items()):
            print(f"obs: wrote {kind} -> {path}")


if __name__ == "__main__":
    main()
