"""Readings that set a cell's limits (``limits/<cell>.json``).

    python3 benchmarks/chip/read_limits.py --workload <cell> \
        --seeds 11,12,... --controls 3 --out <file.json>

For every seed, in one process: the program's compared rounds at the
cell's own size (the same ``run_fedssl`` path and hook a benchmark run
takes, with a window of no seconds) against the plain reference, which
give the lower readings. For the first ``--controls`` seeds also the
control (the reference one precision below the configuration's) and the
program with half of every batch left out, each against the reference,
which give the upper readings. A state left unchanged reads 1 on every
``update_gap`` by construction and needs no run. The benchmark's own runs
never run this.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import faults, harness  # noqa: E402


def program_rounds(run):
    window = harness.Window(0.0)
    run.drive(window)
    out = window.compared()
    del window
    gc.collect()
    jax.clear_caches()
    return out


def read(workload, seeds, controls, **run_kw):
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        run = harness.Run(workload, seed, **run_kw)
        prog = program_rounds(run)
        ref = run.reference()
        row = {"seed": seed, "program": run.numbers(ref, *prog)}
        if i < controls:
            _, ctl_online, ctl_losses, _ = run.reference("control")
            row["control"] = run.numbers(ref, ctl_online, ctl_losses)
            with faults.planted("half_batch"):
                row["half_batch"] = run.numbers(ref, *program_rounds(run))
        row["seconds"] = time.perf_counter() - t
        harness.say(json.dumps(row))
        rows.append(row)
        del run, prog, ref
        gc.collect()
    summary = {}
    for kind, pick in (("program", max), ("control", min),
                       ("half_batch", min)):
        got = [r[kind] for r in rows if kind in r]
        if got:
            summary[kind] = {n: pick(g[n] for g in got) for n in got[0]}
    return {"workload": workload, "rows": rows, "summary": summary}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(harness.ROOT / "src"))
    out = read(args.workload, [int(s) for s in args.seeds.split(",")],
               args.controls)
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out["summary"]))


if __name__ == "__main__":
    main()
