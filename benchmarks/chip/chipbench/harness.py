"""One benchmark run of one cell: set-up, the measured window, the
correctness comparison and the result line.

Everything that belongs to a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration file, ``traffic/<traffic>.json``,
``limits/<cell>.json`` and one reader ``metrics/<metric>.py`` per
per-layer metric. What reads the configuration's model keys (the
program's configuration objects, the inputs, the plain reference and
the FLOP count) is the module ``families/<family>.py`` that the
configuration's top-level ``family`` names.

The window drives the program's own entry, ``repro.federated.driver.
run_fedssl``, once per run, with the vmap engine. The harness sees round
ends through the driver's health hook (``Observability(health=...)``):
the driver calls it after each round, once that round's losses have been
read back, so the round's client program has finished. Round 0 compiles
and warms every program the window uses and counts as set-up. The window
opens once round 0's state is on the device (its calibration included),
runs whole rounds until ``--seconds`` have passed, and closes when the
state of that round end, calibration included, is ready; there the hook
asks the driver to halt. The window therefore holds exactly its rounds'
client programs and calibrations, and the rounds between its ends run
pipelined as the driver runs them unobserved.

``correct`` compares rounds 0 and 1 (``COMPARED_ROUNDS``): round 0 is the
first round of the object the window drives, round 1 the window's first
round, which starts from the state round 0 and its calibration left.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPARED_ROUNDS = 2


def say(msg):
    print(msg, file=sys.stderr, flush=True)


class BenchError(RuntimeError):
    """The run cannot produce a result (wrong device, missing files)."""


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, root=ROOT, bench_dir=BENCH_DIR):
    """(benchmark spec, cell entry, configuration, traffic, limits)."""
    spec = load_json(pathlib.Path(root) / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(pathlib.Path(root) / entry["file"])
    traffic = load_json(pathlib.Path(bench_dir) / "traffic"
                        / f"{cell['traffic']}.json")
    limits = load_json(pathlib.Path(bench_dir) / "limits"
                       / f"{cell['name']}.json")
    return spec, cell, cfg, traffic, limits


def cell_metrics(spec, cell, kind):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def _load_module(kind, name, bench_dir):
    """The module ``<kind>/<name>.py`` of the benchmark, by file path."""
    path = pathlib.Path(bench_dir) / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name, bench_dir=BENCH_DIR):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    return _load_module("metrics", name, bench_dir).read


def load_family(cfg, bench_dir=BENCH_DIR):
    """The module ``families/<family>.py`` of the configuration's
    ``family`` (its interface: ``families/vit.py``)."""
    name = cfg.get("family")
    if (not isinstance(name, str)
            or not (pathlib.Path(bench_dir) / "families"
                    / f"{name}.py").is_file()):
        raise BenchError(f"configuration {cfg.get('name')!r} names model "
                         f"family {name!r}, which has no "
                         f"families/<family>.py")
    return _load_module("families", name, bench_dir)


def device_peak(device_kind, bench_dir=BENCH_DIR):
    peaks = load_json(pathlib.Path(bench_dir) / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise BenchError(f"no peaks for device_kind {device_kind!r} in "
                         f"peaks.json; have {sorted(peaks)}")
    return peaks[device_kind]


# ---------------------------------------------------------------------------
# the program's configuration objects, built from the cell's files
# ---------------------------------------------------------------------------
def program_configs(family, cfg, traffic):
    """(model, ssl, train, fl): the family's three, and the federated
    plan from the traffic."""
    from repro.configs.base import FLConfig
    model, ssl, train = family.program_configs(cfg, traffic)
    L = model.num_layers
    if traffic["schedule"] == "lw_fedssl":
        s = traffic["stage"]
        per_stage = tuple(traffic["rounds"] if i == s - 1 else 0
                          for i in range(L))
    elif traffic["schedule"] == "e2e":
        per_stage = ()
    else:
        raise BenchError(f"no schedule {traffic['schedule']!r}")
    fl = FLConfig(num_clients=traffic["clients"],
                  clients_per_round=traffic["cohort"],
                  rounds=traffic["rounds"],
                  local_epochs=traffic["local_epochs"],
                  schedule=traffic["schedule"], rounds_per_stage=per_stage,
                  server_epochs=traffic.get("server_epochs", 0))
    return model, ssl, train, fl


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
class CompileCounter:
    """Backend compiles (and persistent-cache loads) by host time, from
    JAX's own compile event."""

    def __init__(self):
        import jax
        self.times = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.times.append((time.perf_counter(), secs))

    def between(self, t0, t1):
        return sum(1 for t, _ in self.times if t0 <= t <= t1)

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class Window:
    """The driver's health hook, used as the benchmark's clock.

    ``observe_round`` runs after every round, with the server state in the
    calling frame (the driver's ``state``). At the end of round 0, the
    set-up's, it waits for that state (calibration included), keeps the
    online model on the host for the comparison and opens the window. At
    the end of round 1 it keeps a handle on the online model without
    waiting for it. Once ``seconds`` have passed it waits for the state of
    that round end, closes the window and halts the driver. Rounds in
    between are not waited on, so the driver pipelines them as it does
    unobserved.
    """

    def __init__(self, seconds, on_open=None, on_close=None):
        self.seconds = seconds
        self.on_open, self.on_close = on_open, on_close
        self.losses = []
        self.online = []          # after each of the compared rounds
        self.setup_end = self.t0 = self.t_end = None
        self.rounds = 0
        self.should_halt = False

    @staticmethod
    def driver_state():
        """The server state of the driver's round loop, read from the
        frame that called the hook (``run_fedssl`` passes no state)."""
        caller = sys._getframe(2).f_locals
        if "state" not in caller:
            raise BenchError("the driver's round loop holds no `state`")
        return caller["state"]

    def observe_round(self, round_idx, *, loss, **_):
        import jax
        now = time.perf_counter()
        self.losses.append(loss)
        if round_idx == 0:
            state = jax.block_until_ready(self.driver_state())
            self.online.append(_host_tree(state["online"]))
            del state
            self.setup_end = time.perf_counter()
            if self.on_open:
                self.on_open()
            self.t0 = time.perf_counter()
            return []
        self.rounds += 1
        if round_idx < COMPARED_ROUNDS:
            self.online.append(self.driver_state()["online"])
        if now - self.t0 >= self.seconds:
            jax.block_until_ready(self.driver_state())
            self.t_end = time.perf_counter()
            self.should_halt = True
            if self.on_close:
                self.on_close()
        return []

    def compared(self):
        """(online model after each compared round, on the host; their
        losses). Call once the window has closed."""
        self.online = [_host_tree(t) for t in self.online]
        return self.online, self.losses[:COMPARED_ROUNDS]


def _host_tree(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def memory_peak(device):
    """Peak HBM of the run, from the allocator's two peaks: buffers
    (``peak_bytes_in_use``) and the reservations that hold each program's
    temporaries (``peak_bytes_reserved``, where the cohort's training
    state lives on TPU). They peak at different times (buffers while the
    pool is made, reservations in the round program), so their sum can
    exceed the device; the larger is the least the run held at once.
    Zero where the device reports no statistics (the CPU)."""
    st = device.memory_stats() or {}
    return max(int(st.get("peak_bytes_in_use", 0)),
               int(st.get("peak_bytes_reserved", 0)))


class Run:
    """A cell's files, its device and its inputs for one seed."""

    def __init__(self, workload, seed, *, root=ROOT, bench_dir=BENCH_DIR,
                 require_tpu=True, cell_files=None):
        import jax
        from repro.launch.compile_cache import enable_compile_cache

        self.spec, self.cell, self.cfg, self.traffic, self.limits = (
            cell_files or load_cell)(workload, root, bench_dir)
        self.devices = jax.devices()
        self.dev = self.devices[0]
        if require_tpu and self.dev.platform != "tpu":
            raise BenchError(f"needs a TPU; JAX found platform "
                             f"{self.dev.platform!r}")
        if len(self.devices) < self.cell["chips"]:
            raise BenchError(f"cell asks for {self.cell['chips']} chips; "
                             f"JAX found {len(self.devices)}")
        if self.traffic["engine"] != "vmap":
            raise BenchError("the window drives the vmap engine")
        self.peak = (device_peak(self.dev.device_kind, bench_dir)
                     if require_tpu else None)
        self.root, self.bench_dir, self.seed = root, bench_dir, seed
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.family = load_family(self.cfg, bench_dir)
        self.inputs = self.family.make_inputs(seed, self.traffic, self.cfg)

    def drive(self, window):
        """``run_fedssl`` over the cell's traffic until ``window`` halts
        it; returns the final server state."""
        import jax
        from repro.federated.driver import run_fedssl
        from repro.obs import Observability
        model, ssl, train, fl = program_configs(self.family, self.cfg,
                                                self.traffic)
        tr, inputs = self.traffic, self.inputs
        state, _ = run_fedssl(
            model, ssl, fl, train,
            client_indices=[jax.numpy.asarray(s) for s in inputs.shards],
            key=inputs.key, engine=tr["engine"], codec=tr["codec"],
            transport_kernels=tr["transport_kernels"],
            obs=Observability(health=window),
            **self.family.run_kwargs(inputs, self.cfg))
        if not window.should_halt:
            raise BenchError(f"the traffic's {tr['rounds']} rounds ended "
                             f"before the window did")
        return jax.block_until_ready(state)

    def reference(self, numerics="reference"):
        """(initial online model, [online model after each compared round],
        [their mean last-step client losses], [their first-gradient
        norms]) of the plain reference, on the host; ``numerics="control"``
        gives the control."""
        from chipbench import compare
        t = time.perf_counter()
        init, rounds = self.family.reference_rounds(
            self.cfg, self.traffic, numerics, self.inputs, COMPARED_ROUNDS)
        say(f"{numerics} rounds 0-{COMPARED_ROUNDS - 1} in "
            f"{time.perf_counter() - t:.1f}s, losses "
            f"{[r[1] for r in rounds]!r}")
        return (_host_tree(init), [_host_tree(r[0]) for r in rounds],
                [r[1] for r in rounds],
                [compare.leaf_paths(_host_tree(r[2])) for r in rounds])

    @staticmethod
    def numbers(ref, prog_online, prog_losses):
        """The compared numbers of each compared round against the
        reference's: ``loss_gap.r<i>`` and ``update_gap.r<i>``, where
        round i's update is its online model less the one it started from
        (the initial weights for round 0), each side its own."""
        from chipbench import compare
        init, ref_online, ref_losses, ref_grads = ref
        out = {}
        for i, (p_after, r_after) in enumerate(zip(prog_online, ref_online)):
            p_before = init if i == 0 else prog_online[i - 1]
            r_before = init if i == 0 else ref_online[i - 1]
            gap, worst, n_used, n_still = compare.update_gap(
                compare.change_norms(p_after, p_before),
                compare.change_norms(r_after, r_before),
                {p: float(g) for p, g in ref_grads[i].items()})
            say(f"round {i} update gap worst leaf {worst} over {n_used} "
                f"leaves ({n_still} left out: first gradient nought to "
                f"rounding)")
            out[f"loss_gap.r{i}"] = compare.loss_gap(prog_losses[i],
                                                     ref_losses[i])
            out[f"update_gap.r{i}"] = gap
        return out


def run_cell(workload, seed, seconds, trace, *, t_start, **run_kw):
    """One benchmark run; returns the result object (the last line)."""
    import jax

    from chipbench import compare, scopes, xplane

    run = Run(workload, seed, **run_kw)
    cell, traffic = run.cell, run.traffic
    compiles = CompileCounter()
    trace_dir = pathlib.Path(run.root) / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(
        seconds,
        on_open=(lambda: jax.profiler.start_trace(str(trace_dir)))
        if trace else None,
        on_close=jax.profiler.stop_trace if trace else None)
    state = run.drive(window)
    window_s = window.t_end - window.t0
    mem_peak = memory_peak(run.dev)
    window_losses = window.losses[1:]
    n_compiles = compiles.between(window.t0, window.t_end)
    compiles.close()
    say(f"set-up {window.setup_end - t_start:.3f}s; window {window.rounds} "
        f"rounds in {window_s:.3f}s, {n_compiles} compiles in it; losses "
        f"{window.losses}")
    # the reference replays the compared rounds once the window is closed
    # and the program's state and compiled programs are freed
    prog_online, prog_losses = window.compared()
    del state
    gc.collect()
    jax.clear_caches()
    numbers = run.numbers(run.reference(), prog_online, prog_losses)
    correct, checks = compare.judge(numbers, run.limits["limits"])
    failed = sum(1 for x in window_losses if not math.isfinite(x))

    # what a per-layer metric's reader may read
    ctx = SimpleNamespace(
        cell=cell, cfg=run.cfg, traffic=traffic, rounds=window.rounds,
        window_s=window_s, compiles=n_compiles,
        round_flops=run.family.round_flops(run.cfg, traffic), peak=run.peak,
        trace=None, scopes=None)
    device = {"platform": run.dev.platform, "kind": run.dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct and failed == 0),
              "attempted": window.rounds, "failed": failed}
    if trace:
        path = xplane.find_xplane(trace_dir)
        ctx.trace = xplane.reduce_trace(path, cell["chips"])
        ctx.scopes = scopes.reduce_trace(path, cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = window_s
        metrics = {}
        for m in cell_metrics(run.spec, cell, "per_layer"):
            v = load_reader(m["name"], run.bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = ctx.trace.breakdown()
    else:
        values = {
            "client_samples_per_s":
                window.rounds * ctx.round_flops["client_samples"] / window_s,
            "peak_hbm_bytes": mem_peak,
            "setup_s": window.setup_end - t_start}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(run.spec, cell, "end_to_end")}
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv, t_start):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip benchmark: the program is not in {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0

