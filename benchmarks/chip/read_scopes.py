"""Per-scope split of a cell's window rounds, from traced windows.

    python3 benchmarks/chip/read_scopes.py --workload <cell> \
        --seeds 11,12,... --seconds <s> --out <file.json> [--keep <dir>]

For every seed, in one process: the cell's window, as a benchmark run
drives it (``harness.Run`` and ``harness.Window``), with the profiler on
from the window's opening to its close. The trace is reduced by
``chipbench.xplane`` and ``chipbench.scopes`` to the cell's per-layer
metrics as a benchmark run's readers read them, with the share of each
program's op time that carries a scope, the heaviest op paths, the host
spans and the traced window's client samples per second.
No reference runs. ``--keep`` copies each run's ``.xplane.pb`` there.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import pathlib
import shutil
import sys
import time
from types import SimpleNamespace

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import harness, scopes, xplane  # noqa: E402


def top_paths(trace, n, keep=lambda p: True):
    """The ``n`` op_name paths of the round program that ``keep`` accepts
    with the most op self time, ms per device over the window."""
    paths = [(p, v) for p, v in trace.op_ns.get(scopes.ROUND_MODULE,
                                                  {}).items() if keep(p)]
    top = sorted(paths, key=lambda kv: -kv[1])[:n]
    return [[p, 1e-6 * v / trace.devices] for p, v in top]


def read(workload, seed, seconds, keep=None, **run_kw):
    run = harness.Run(workload, seed, **run_kw)
    trace_dir = pathlib.Path(run.root) / ".bench_trace" / "scopes"
    shutil.rmtree(trace_dir, ignore_errors=True)
    window = harness.Window(
        seconds, on_open=lambda: jax.profiler.start_trace(str(trace_dir)),
        on_close=jax.profiler.stop_trace)
    compiles = harness.CompileCounter()
    state = run.drive(window)
    window_s = window.t_end - window.t0
    n_compiles = compiles.between(window.t0, window.t_end)
    compiles.close()
    del state
    path = xplane.find_xplane(trace_dir)
    if keep:
        pathlib.Path(keep).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, pathlib.Path(keep) / f"{workload}.{seed}.xplane.pb")
    chips = run.cell["chips"]
    st = scopes.reduce_trace(path, chips)
    ctx = SimpleNamespace(
        cell=run.cell, cfg=run.cfg, traffic=run.traffic,
        rounds=window.rounds, window_s=window_s, compiles=n_compiles,
        round_flops=run.family.round_flops(run.cfg, run.traffic),
        peak=run.peak, trace=xplane.reduce_trace(path, chips), scopes=st)
    shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in harness.cell_metrics(run.spec, run.cell, "per_layer"):
        v = harness.load_reader(m["name"], run.bench_dir)(ctx)
        if v is not None:
            metrics[m["name"]] = v
    return {
        "seed": seed, "rounds": window.rounds, "window_s": window_s,
        "busy_s": ctx.trace.busy_s,
        "client_samples_per_s": (window.rounds
                                 * ctx.round_flops["client_samples"]
                                 / window_s),
        "metrics": metrics,
        "scoped_share": {m: st.scoped_share(m) for m in st.op_ns},
        "module_ms": {m: 1e-6 * st.scope_ns(m, lambda p: True)
                      / window.rounds for m in st.op_ns},
        "top_paths": top_paths(st, 40),
        "unscoped": top_paths(st, 15,
                              lambda p: not scopes.path_scopes(p)),
        "spans": span_totals(st),
        "breakdown": ctx.trace.breakdown(),
    }


def span_totals(trace):
    """{host span name: [count, summed ms]} of the traced window."""
    out = {}
    for s, e, name in trace.spans:
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += 1e-6 * (e - s)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep")
    args = ap.parse_args()
    sys.path.insert(0, str(harness.ROOT / "src"))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = read(args.workload, seed, args.seconds, args.keep)
        row["seconds"] = time.perf_counter() - t
        harness.say(json.dumps({k: row[k] for k in (
            "seed", "rounds", "window_s", "client_samples_per_s", "metrics",
            "scoped_share", "seconds")}))
        rows.append(row)
        gc.collect()
        jax.clear_caches()
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(rows, indent=1))
    print(json.dumps([r["metrics"] for r in rows]))


if __name__ == "__main__":
    main()
