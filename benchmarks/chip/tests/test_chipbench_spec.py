"""BENCHMARK.json resolves to the benchmark's files, and every per-layer
metric's end-to-end target is reported by each cell it names."""
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_entry_resolves_to_its_files():
    s = spec()
    assert s["paths"] == ["benchmarks/chip"]
    assert s["command"][1] == "benchmarks/chip/run.py"
    configs = {c["name"]: c for c in s["configs"]}
    for c in s["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert (BENCH / "families" / f"{cfg['family']}.py").is_file()
    for w in s["workloads"]:
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json")
                            .read_text())["limits"]
        assert set(limits) == {f"{n}.r{i}" for n in ("loss_gap", "update_gap")
                               for i in range(2)}
    for m in s["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_names_units_and_bounds_keep_the_contract():
    s = spec()
    names = ([c["name"] for c in s["configs"]]
             + [w["name"] for w in s["workloads"]]
             + [m["name"] for m in s["end_to_end"] + s["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert 1 <= s["run_seconds"] <= 51
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in s["end_to_end"]}
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_each_per_layer_target_is_reported_where_it_is_read():
    s = spec()
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    for m in s["per_layer"]:
        target = e2e[m["moves"]]
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(target.get("workloads", cells))
    for w in cells:
        assert any(w in m["workloads"] for m in s["per_layer"])
