"""Bring-up check: federated MoCo v3 training of ViT-Tiny on one TPU.

    python chip_smoke.py

Runs the main path once, in one process, through the launcher's own
configs (``repro.launch.train.vit_configs``: the published ViT-Tiny with
its 4096/4096/256 MoCo v3 heads and per-block remat) and round loop
(``repro.federated.driver.run_fedssl``), at the published batch of 1024,
with random weights and synthetic images made from a seed:

  A  full width on the vmap engine, ``COHORT`` clients per round: two
     ``e2e`` rounds (the memory peak: all 12 blocks trained, the whole
     26M-element payload on the wire), then ``lw_fedssl`` through its
     first stage transition (weight transfer, alignment, server
     calibration);
  B  engine parity: one ``e2e`` round on the sequential and on the vmap
     engine from one seed; the round losses agree within
     ``PARITY_RTOL`` at the default matmul precision;
  C  the Pallas wire kernels against the XLA wire path on ViT-Tiny
     payloads: pack/unpack and fp32 rounds bit-identical, int8 within one
     quantum, top-k the same selected set.

Every phase checks its own results and raises on a mismatch; the script
exits non-zero on any failure, and before any work if JAX finds no TPU.
Lines before the last are bring-up facts (losses, compile seconds per
program, the allocator's peaks), not benchmark numbers. The last line is
one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

COHORT = 8            # clients per round that fit at batch 1024 with remat
BATCH = 1024
PARITY_RTOL = 1e-2    # sequential vs vmap round losses, default precision
CODEC_RTOL = 1e-2     # int8/top-k round losses, pallas vs xla wire path


def say(msg):
    print(f"bring-up: {msg}", flush=True)


class CompileLog:
    """Seconds each program took to compile (or to load from the
    persistent cache), from JAX's own backend-compile event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((kw.get("fun_name", "?"), secs))

    def report(self, phase):
        big = [(n, s) for n, s in self.events if s >= 1.0]
        say(f"{phase}: {len(self.events)} programs compiled in "
            f"{sum(s for _, s in self.events):.1f}s; over 1s: "
            + ", ".join(f"{n} {s:.1f}s" for n, s in big))
        self.events = []


def peak_bytes():
    """The allocator's peaks so far: buffers (``peak_bytes_in_use``) and
    the reservations that hold each program's temporaries
    (``peak_bytes_reserved``), against the device's ``bytes_limit``."""
    st = jax.devices()[0].memory_stats()
    return (f"peak_bytes_in_use {st['peak_bytes_in_use']}, "
            f"peak_bytes_reserved {st.get('peak_bytes_reserved')}, "
            f"bytes_limit {st.get('bytes_limit')}")


def fedssl(cfgs, *, schedule, engine, clients, rounds, seed=0, codec="fp32",
           kernels="xla", rounds_per_stage=()):
    """One ``run_fedssl`` over ``clients`` IID shards of one batch each."""
    from repro.configs.base import FLConfig
    from repro.data import iid_partition, synthetic_images
    from repro.federated.driver import run_fedssl

    cfg, ssl_cfg, tc = cfgs
    fl = FLConfig(num_clients=clients, rounds=rounds, local_epochs=1,
                  schedule=schedule, server_epochs=1,
                  rounds_per_stage=rounds_per_stage)
    key = jax.random.PRNGKey(seed)
    kd, key = jax.random.split(key)
    n = clients * tc.batch_size
    images, _ = synthetic_images(kd, n, 10, 32)
    shards = [jnp.asarray(i) for i in iid_partition(n, clients, seed=seed)]
    state, hist = run_fedssl(
        cfg, ssl_cfg, fl, tc, images=images, client_indices=shards,
        aux_images=images[:tc.batch_size], key=key, engine=engine,
        codec=codec, transport_kernels=kernels)
    if not np.all(np.isfinite(hist.loss)):
        raise RuntimeError(f"{schedule}/{engine}: non-finite loss "
                           f"{hist.loss}")
    return state, hist


def phase_a(cfgs, clients, log):
    t = time.perf_counter()
    _, hist = fedssl(cfgs, schedule="e2e", engine="vmap", clients=clients,
                     rounds=2)
    say(f"A e2e vmap, {clients} clients x batch "
        f"{cfgs[2].batch_size}: round losses {hist.loss}, "
        f"{time.perf_counter() - t:.1f}s with compile, {peak_bytes()}")
    log.report("A e2e")
    stages = cfgs[0].num_layers
    t = time.perf_counter()
    _, hist = fedssl(cfgs, schedule="lw_fedssl", engine="vmap",
                     clients=clients, rounds=2,
                     rounds_per_stage=(1, 1) + (0,) * (stages - 2))
    if hist.round_stage != [1, 2]:
        raise RuntimeError(f"lw_fedssl ran stages {hist.round_stage}")
    say(f"A lw_fedssl stages 1-2 vmap: round losses {hist.loss}, "
        f"{time.perf_counter() - t:.1f}s with compile, {peak_bytes()}")
    log.report("A lw_fedssl")


def phase_b(cfgs, clients, log):
    losses = {}
    for engine in ("sequential", "vmap"):
        _, hist = fedssl(cfgs, schedule="e2e", engine=engine,
                         clients=clients, rounds=1, seed=1)
        losses[engine] = hist.loss[0]
    rel = abs(losses["sequential"] - losses["vmap"]) / abs(
        losses["sequential"])
    say(f"B engine parity, e2e round 1: sequential {losses['sequential']!r} "
        f"vmap {losses['vmap']!r}, relative difference {rel:.3g} "
        f"(tolerance {PARITY_RTOL}, default matmul precision)")
    log.report("B")
    if not rel <= PARITY_RTOL:
        raise RuntimeError("engine parity outside tolerance")


def _wire_checks(cfgs):
    """Pallas wire kernels vs the XLA wire path on ViT-Tiny payloads."""
    from repro.core import schedule as sched
    from repro.core import ssl as ssl_mod
    from repro.configs.base import FLConfig
    from repro.federated import transport as tr
    from repro.kernels import ops as kops

    cfg, ssl_cfg, _ = cfgs
    enc = ssl_mod.make_vit_encoder(cfg)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    online = ssl_mod.ssl_init(keys[0], enc, ssl_cfg)["online"]
    other = ssl_mod.ssl_init(keys[1], enc, ssl_cfg)["online"]
    for schedule, at in (("e2e", 0), ("lw_fedssl", cfg.num_layers // 2 - 1)):
        plan = sched.build_schedule(
            FLConfig(schedule=schedule, rounds=cfg.num_layers),
            cfg.num_layers)[at]
        spec = tr.Transport("fp32").plan_specs(online, plan)["upload"]
        flat = jax.jit(lambda t: tr.pack_stage_payload(t, spec))(online)
        if not np.array_equal(np.asarray(tr.kernel_pack(online, spec)),
                              np.asarray(flat)):
            raise RuntimeError(f"{schedule}: pallas pack != xla pack")
        want = jax.jit(lambda b, f: tr.unpack_stage_payload(b, f, spec))(
            other, flat)
        got = tr.kernel_unpack(other, flat, spec)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise RuntimeError(f"{schedule}: pallas unpack != xla")
        say(f"C pack/unpack {schedule} plan {at}: {len(spec.slots)} slots, "
            f"{spec.total} elements, bit-identical")
        if schedule != "e2e":
            continue
        codec = tr.Int8Codec()
        wq = jax.jit(lambda f: codec.encode(f, spec))(flat)
        segs, nscales = tr.int8_segs(spec)
        q, scales = kops.wire_int8_encode(flat, segs, nscales)
        dq = int(np.max(np.abs(np.asarray(q, np.int32)
                               - np.asarray(wq["q"], np.int32))))
        ds = float(np.max(np.abs(np.asarray(scales)
                                 - np.asarray(wq["scale"]))))
        if dq > 1 or not np.allclose(scales, wq["scale"], rtol=1e-6):
            raise RuntimeError(f"int8: q off by {dq}, scales by {ds}")
        say(f"C int8 encode, e2e payload: q within {dq} quantum of xla, "
            f"scales max abs difference {ds!r}")
        topk = tr.TopKCodec()
        k = topk.k_for(spec)
        ref = jax.jit(lambda t: tr.pack_stage_payload(t, spec))(other)
        res = 0.01 * jax.random.normal(keys[2], flat.shape)
        x = (flat - ref) + res
        widx = jax.jit(lambda v: topk.encode(v, spec)["idx"])(x)
        idx, _, new_res = kops.wire_topk_encode_ef(flat, ref, res, k)
        same = np.array_equal(np.sort(np.asarray(idx)),
                              np.sort(np.asarray(widx)))
        wres = x.at[widx].set(0.0)
        if not same or not np.array_equal(np.asarray(new_res),
                                          np.asarray(wres)):
            raise RuntimeError("top-k: selected set or residual differs")
        say(f"C top-k encode, e2e payload: k={k}, same selected set, "
            f"bit-identical residual")
    # ties at the threshold in every row tile: the kernel's tie rank must
    # reproduce lax.top_k's lowest-index-first order exactly
    tied = jnp.asarray(np.tile(np.float32([2.0, -1.0, 1.0, 0.5]), 20000))
    zero = jnp.zeros_like(tied)
    idx, _, new_res = kops.wire_topk_encode_ef(tied, zero, zero, 30000)
    widx = jax.lax.top_k(jnp.abs(tied), 30000)[1]
    if not (np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(widx)))
            and np.array_equal(np.asarray(new_res),
                               np.asarray(tied.at[widx].set(0.0)))):
        raise RuntimeError("top-k: tie order differs from lax.top_k")
    say("C top-k ties across row tiles: same selected set as lax.top_k")


def phase_c(cfgs, clients, log):
    _wire_checks(cfgs)
    for codec in ("fp32", "int8", "topk"):
        runs = {k: fedssl(cfgs, schedule="e2e", engine="sequential",
                          clients=clients, rounds=1, seed=3, codec=codec,
                          kernels=k) for k in ("xla", "pallas")}
        lx, lp = runs["xla"][1].loss[0], runs["pallas"][1].loss[0]
        if codec == "fp32":
            same = all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree.leaves(runs["xla"][0]),
                                       jax.tree.leaves(runs["pallas"][0])))
            if not (same and lx == lp):
                raise RuntimeError("fp32 round: pallas != xla")
            note = "bit-identical state"
        else:
            rel = abs(lx - lp) / abs(lx)
            if not rel <= CODEC_RTOL:
                raise RuntimeError(f"{codec} round: loss off by {rel}")
            note = f"relative difference {rel:.3g} (tolerance {CODEC_RTOL})"
        say(f"C {codec} e2e round, sequential, {clients} clients: loss xla "
            f"{lx!r} pallas {lp!r}, {note}")
    log.report("C")


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r}; refusing to run on it")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import vit_configs

    say(f"cache {enable_compile_cache()}")
    say(f"device_kind {dev.device_kind!r}, {len(jax.devices())} devices, "
        f"jax {jax.__version__}")
    log = CompileLog()
    cfg, ssl_cfg, tc = vit_configs()
    cfgs = (cfg, ssl_cfg, dataclasses.replace(tc, batch_size=BATCH))
    t0 = time.perf_counter()
    phase_a(cfgs, COHORT, log)
    phase_b(cfgs, COHORT, log)
    phase_c(cfgs, 2, log)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s, "
        f"{peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
