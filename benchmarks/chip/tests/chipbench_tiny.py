"""A tiny cell for CPU rehearsals: the real harness, the real program and
the real reference at a size a test run holds."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# float32 products: the cells' limits were set for bfloat16 rounding at
# the published widths, not at this size; in float32 a sound tiny run
# matches the reference to rounding, and the control and the planted
# faults still fail the cells' limits
TINY_MODEL = {"num_layers": 2, "d_model": 32, "num_heads": 2, "d_ff": 64,
              "compute_dtype": "float32"}
TINY_SSL = {"proj_dim": 16, "proj_hidden": 64, "pred_hidden": 64}
TINY_TRAFFIC = {"pool": 256, "clients": 4, "cohort": 2, "local_epochs": 1,
                "batch": 16, "rounds": 12}


def load(rel):
    with open(BENCH / rel) as f:
        return json.load(f)


def tiny_cell(cell_name, schedule=None):
    """(spec, cell, cfg, traffic, limits) of ``cell_name`` with its
    configuration and traffic shrunk, and ``schedule`` in place of the
    traffic's own where given; the cell's own limits."""
    spec = load("../../BENCHMARK.json")
    cell = {c["name"]: c for c in spec["workloads"]}[cell_name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = copy.deepcopy(load("../../" + entry["file"]))
    cfg["model"].update(TINY_MODEL)
    cfg["ssl"].update(TINY_SSL)
    traffic = load(f"traffic/{cell['traffic']}.json")
    traffic.update(TINY_TRAFFIC)
    if schedule == "e2e":
        for k in ("stage", "aux", "server_epochs"):
            traffic.pop(k, None)
        traffic["schedule"] = "e2e"
    if traffic["schedule"] == "lw_fedssl":
        traffic.update(stage=2, aux=32, server_epochs=1)
    limits = load(f"limits/{cell['name']}.json")
    return spec, cell, cfg, traffic, limits


def run_tiny(cell_name, seed=12345, seconds=0.5, trace=False,
             schedule=None):
    from chipbench import harness
    files = tiny_cell(cell_name, schedule)
    return harness.run_cell(cell_name, seed, seconds, trace, t_start=0.0,
                            require_tpu=False,
                            cell_files=lambda *_: files)
