"""Resource observatory + health monitoring + regression gating.

Covers the measured-resources module (``repro.obs.resources``): live
memory snapshots, the analytic-vs-XLA FLOPs cross-check on reduced
vit-tiny stages, and the compiled-program memory check; the streaming
``HealthMonitor`` (unit detectors + end-to-end NaN injection with
halt-on-fatal, and bit-identity of health-monitored runs on both
engines); golden-output tests for the trace CLI's round-time breakdown
and comm tables; the provenance header and resources/health schemas; and
the ``benchmarks.compare`` regression gate (drift detection, row
coverage, nonzero exit).
"""
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare as compare_mod
from benchmarks import schemas
from benchmarks.provenance import provenance
from repro.configs.base import FLConfig, ModelConfig, SSLConfig, TrainConfig
from repro.data import iid_partition, synthetic_images
from repro.federated.driver import run_fedssl
from repro.launch import trace as trace_cli
from repro.obs import HealthMonitor, make_obs, write_health_json
from repro.obs import resources as res_mod
from repro.obs.trace import Tracer
from repro.roofline.client_costs import PAPER_MULT

ROOT = pathlib.Path(__file__).resolve().parents[1]

CFG = ModelConfig("t-vit", "dense", 2, 32, 2, 2, 64, 0, causal=False,
                  compute_dtype="float32", act="gelu")
SSLC = SSLConfig(proj_hidden=32, pred_hidden=32, proj_dim=16)
TC = TrainConfig(batch_size=16, base_lr=1.5e-4)


def _run(engine="sequential", obs=None, rounds=2, schedule="lw_fedssl",
         images=None, seed=0):
    key = jax.random.PRNGKey(seed)
    if images is None:
        images, _ = synthetic_images(key, 96, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(96, 3)]
    fl = FLConfig(num_clients=3, rounds=rounds, local_epochs=1,
                  schedule=schedule, server_epochs=1)
    return run_fedssl(CFG, SSLC, fl, TC, images=images, client_indices=idx,
                      aux_images=images[:16], key=key, engine=engine,
                      obs=obs)


# ---------------------------------------------------------------------------
# live memory watermarks + mem.* span-attr filtering
# ---------------------------------------------------------------------------
def test_device_memory_snapshot_cpu():
    snap = res_mod.device_memory_snapshot()
    assert snap["source"] in ("device", "rss")
    assert snap["bytes_in_use"] > 0
    assert snap["peak_bytes"] >= snap["bytes_in_use"] or \
        snap["source"] == "device"
    attrs = res_mod.memory_span_attrs()
    assert set(attrs) == {"mem.source", "mem.bytes_in_use",
                          "mem.peak_bytes"}


class _FakeTPU:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("in_use_peak,reserved_peak", [(20, 50), (50, 20)])
def test_device_memory_snapshot_reads_the_larger_peak(in_use_peak,
                                                      reserved_peak):
    """On TPU a program's temporaries count in ``peak_bytes_reserved``,
    not in ``peak_bytes_in_use``: the snapshot's peak is the larger."""
    snap = res_mod.device_memory_snapshot(_FakeTPU({
        "bytes_in_use": 10, "peak_bytes_in_use": in_use_peak,
        "peak_bytes_reserved": reserved_peak}))
    assert snap == {"source": "device", "bytes_in_use": 10,
                    "peak_bytes": 50}


def test_memory_attrs_only_on_traced_rounds(monkeypatch):
    """The driver queries device memory for the round span only when the
    in-memory tracer records it: a health hook alone never pays for it."""
    def no_query(*a, **k):
        raise AssertionError("memory queried on an untraced run")

    monkeypatch.setattr(res_mod, "memory_span_attrs", no_query)
    _run(engine="vmap", obs=make_obs(health=True), rounds=1, schedule="e2e")


def test_structure_ignores_mem_attrs():
    """mem.* attrs vary per machine/run; the determinism fingerprint
    must not see them (the driver stamps them on every round span)."""
    tracers = []
    for peak in (111, 222):
        t = Tracer()
        with t.span("round", cat="fl", round=0) as sp:
            sp.set(loss=1.0)
            sp.set(**{"mem.source": "rss", "mem.bytes_in_use": peak,
                      "mem.peak_bytes": peak})
        tracers.append(t)
    assert tracers[0].structure() == tracers[1].structure()
    # the attrs themselves are still on the event for the trace readers
    assert tracers[0].events[0]["args"]["mem.peak_bytes"] == 111


# ---------------------------------------------------------------------------
# health monitor: unit detectors
# ---------------------------------------------------------------------------
def test_health_nonfinite_is_fatal_and_halts():
    m = HealthMonitor(halt_on_fatal=True)
    assert m.observe_round(0, loss=1.0) == []
    alerts = m.observe_round(1, loss=float("nan"))
    assert [a.kind for a in alerts] == ["loss_nonfinite"]
    assert alerts[0].level == "fatal"
    assert m.fatal and m.should_halt
    assert not HealthMonitor(halt_on_fatal=False).should_halt
    inf_alerts = m.observe_round(2, loss=float("inf"))
    assert inf_alerts[0].kind == "loss_nonfinite"
    assert inf_alerts[0].to_dict()["value"] is None      # json-safe


def test_health_loss_spike_zscore_and_stage_reset():
    m = HealthMonitor(loss_z=4.0, warmup=3)
    rng = np.random.RandomState(0)
    for i in range(8):
        assert m.observe_round(i, loss=1.0 + 1e-3 * rng.randn()) == []
    alerts = m.observe_round(8, loss=5.0)
    assert [a.kind for a in alerts] == ["loss_spike"]
    assert alerts[0].level == "warn" and alerts[0].value > 4.0
    # a new stage resets the distribution: the same jump right after a
    # stage transition is a new loss scale, not a spike
    assert m.observe_round(9, loss=5.0, new_stage=True) == []
    assert m.observe_round(10, loss=5.0) == []


def test_health_compression_drift_per_stage_reference():
    m = HealthMonitor(ratio_rtol=0.25)
    assert m.observe_round(0, loss=1.0, compression_ratio=4.0) == []
    assert m.observe_round(1, loss=1.0, compression_ratio=4.5) == []
    alerts = m.observe_round(2, loss=1.0, compression_ratio=8.0)
    assert [a.kind for a in alerts] == ["compression_drift"]
    # stage transition re-bases the reference ratio
    assert m.observe_round(3, loss=1.0, compression_ratio=8.0,
                           new_stage=True) == []


def test_health_drop_rate_and_recompile_storm():
    m = HealthMonitor(drop_rate_max=0.5, warmup=2)
    for i in range(2):       # inside warmup: never flagged
        assert m.observe_round(i, loss=1.0, dropped=2, participants=1) == []
    alerts = m.observe_round(2, loss=1.0, dropped=2, participants=1)
    assert [a.kind for a in alerts] == ["drop_rate"]
    # recompiles on a stage-opening round are legal retraces
    m2 = HealthMonitor()
    assert m2.observe_round(0, loss=1.0, recompiles=2, new_stage=True) == []
    alerts = m2.observe_round(1, loss=1.0, recompiles=1)
    assert [a.kind for a in alerts] == ["recompile_storm"]


def test_health_report_schema_and_export(tmp_path):
    m = HealthMonitor()
    m.observe_round(0, loss=1.0)
    m.observe_round(1, loss=float("nan"))
    rep = m.report()
    assert schemas.validate_health_report(rep) == []
    assert rep["counts"]["loss_nonfinite"] == 1 and rep["fatal"]
    out = tmp_path / "health.json"
    doc = write_health_json(out, m, schedule="lw_fedssl")
    reread = json.loads(out.read_text())
    assert schemas.validate_health_report(reread) == []
    assert reread["meta"]["schedule"] == "lw_fedssl" == \
        doc["meta"]["schedule"]
    # the validator catches cooked documents
    bad = dict(rep, counts=dict(rep["counts"], loss_spike=7))
    assert schemas.validate_health_report(bad) != []
    assert schemas.validate_health_report(
        dict(rep, fatal=False, halted=True)) != []


# ---------------------------------------------------------------------------
# health monitor: end-to-end through the driver
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_nan_injection_flags_and_halts(tmp_path):
    """A NaN-poisoned batch must raise a fatal loss_nonfinite alert on
    the trace, truncate the run under --halt-on-unhealthy, and export a
    schema-valid health.json."""
    imgs, _ = synthetic_images(jax.random.PRNGKey(0), 96, 10, 32)
    bad = np.asarray(imgs).copy()
    bad[:] = np.nan
    obs = make_obs(trace=True, health=True, halt_on_unhealthy=True)
    _, hist = _run(obs=obs, rounds=3, images=jnp.asarray(bad))
    assert len(hist.loss) == 1 and math.isnan(hist.loss[0])
    assert obs.health.fatal and obs.health.should_halt
    kinds = [e["name"] for e in obs.tracer.events if e["cat"] == "health"]
    assert "health.loss_nonfinite" in kinds and "health.halt" in kinds
    out = tmp_path / "health.json"
    obs.export(health_json=out, schedule="lw_fedssl")
    doc = json.loads(out.read_text())
    assert schemas.validate_health_report(doc) == []
    assert doc["halted"] is True
    # without the halt hook the run finishes all rounds, still flagged
    obs2 = make_obs(health=True)
    _, hist2 = _run(obs=obs2, rounds=3, images=jnp.asarray(bad))
    assert len(hist2.loss) == 3 and obs2.health.fatal
    assert not obs2.health.should_halt


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["sequential", "vmap"])
def test_health_monitoring_is_bit_identical(engine):
    """The monitor observes host-side scalars only: a healthy run with
    health (+trace) enabled must train byte-identically to an
    unmonitored one, and raise nothing."""
    s_off, h_off = _run(engine=engine, obs=None)
    obs = make_obs(trace=True, health=True, halt_on_unhealthy=True)
    s_on, h_on = _run(engine=engine, obs=obs)
    assert obs.health.alerts == [] and not obs.health.fatal
    for a, b in zip(jax.tree.leaves(s_off["online"]),
                    jax.tree.leaves(s_on["online"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert h_off.loss == h_on.loss


# ---------------------------------------------------------------------------
# measured resources: analytic roofline vs XLA cost/memory analysis
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_flops_crosscheck_analytic_vs_xla():
    """Per-stage XLA cost_analysis FLOPs (unrolled lowering) must agree
    with the analytic roofline within FLOPS_RTOL on reduced vit-tiny
    stages — for both the layer-wise schedule's stage shapes and the
    vmap engine's fused round program."""
    cfg, ssl, train = res_mod.measurement_config(num_layers=2, batch_size=4)
    m = res_mod.measure_schedule("lw_fedssl", "sequential", cfg=cfg,
                                 ssl=ssl, train=train, rounds=4,
                                 compile_memory=False)
    assert len(m["stages"]) == 2 and m["peak_memory"] is None
    for s in m["stages"]:
        ratio = s["flops_per_sample"] / s["analytic_flops_per_sample"]
        assert abs(ratio - 1.0) <= res_mod.FLOPS_RTOL, s
        assert ratio >= 1.0    # XLA counts ops the roofline folds away
    assert abs(m["flops_total"] / m["analytic_flops_total"] - 1.0) \
        <= res_mod.FLOPS_RTOL
    mv = res_mod.measure_schedule("e2e", "vmap", cfg=cfg, ssl=ssl,
                                  train=train, rounds=2,
                                  compile_memory=False, clients=2)
    ratio = mv["flops_total"] / mv["analytic_flops_total"]
    assert abs(ratio - 1.0) <= res_mod.FLOPS_RTOL


@pytest.mark.slow
def test_memory_crosscheck_compiled_program():
    """Compiled-program peak bytes (memory_analysis of the rolled
    program) must land within MEMORY_FACTOR of the program-aware
    analytic prediction."""
    cfg, ssl, train = res_mod.measurement_config(num_layers=2, batch_size=4)
    m = res_mod.measure_schedule("e2e", "sequential", cfg=cfg, ssl=ssl,
                                 train=train, rounds=2,
                                 compile_memory=True)
    assert m["peak_memory"] and m["argument_bytes"] and m["output_bytes"]
    ratio = m["peak_memory"] / m["program_peak_analytic"]
    assert 1.0 / res_mod.MEMORY_FACTOR <= ratio <= res_mod.MEMORY_FACTOR, m


def test_unrolled_scans_restores_flag():
    from repro.models import scan_cfg
    assert scan_cfg.UNROLL is False
    with pytest.raises(RuntimeError):
        with res_mod.unrolled_scans():
            assert scan_cfg.UNROLL is True
            raise RuntimeError("boom")
    assert scan_cfg.UNROLL is False


# ---------------------------------------------------------------------------
# trace CLI: golden output for breakdown + comm tables
# ---------------------------------------------------------------------------
def _span(name, cat, dur, **args):
    return {"ph": "X", "name": name, "cat": cat, "ts": 0, "dur": dur,
            "pid": 0, "tid": 0, "seq": 0, "parent": None, "depth": 0,
            "args": args}


def test_breakdown_golden_output(capsys):
    events = [
        _span("run", "fl", 4_000_000, schedule="lw_fedssl",
              engine="sequential", codec="fp32"),
        _span("round", "fl", 2_000_000, round=0),
        _span("round", "fl", 2_000_000, round=1),
        _span("local_train", "fl", 1_500_000),
        _span("local_train", "fl", 1_500_000),
        _span("client", "sim", 9_000_000),      # virtual track: excluded
    ]
    trace_cli.print_breakdown("run.jsonl", events)
    assert capsys.readouterr().out == (
        "\n-- run.jsonl: schedule=lw_fedssl engine=sequential codec=fp32\n"
        "   span                      count      total       mean\n"
        "   run                           1     4.000s  4000.00ms\n"
        "   round                         2     4.000s  2000.00ms\n"
        "   local_train                   2     3.000s  1500.00ms\n")


def test_comm_table_golden_output(capsys):
    def trace(schedule, down, up):
        events = [
            _span("run", "fl", 1, schedule=schedule, codec="fp32"),
            _span("round", "fl", 1, download_bytes=down, upload_bytes=up,
                  wire_download_bytes=down, wire_upload_bytes=up),
        ]
        return {"schedule": schedule}, events

    rows = trace_cli.comm_table([trace("e2e", 10_000_000, 10_000_000),
                                 trace("layerwise", 1_000_000, 1_000_000)])
    trace_cli.print_comm_table(rows)
    out = capsys.readouterr().out
    assert out == (
        "\n== comm totals (from round spans) ==\n"
        "schedule     rounds   down(MB)     up(MB)   wire(MB)"
        "   down x     up x   comm x\n"
        "e2e               1       10.0       10.0       20.0"
        "     1.00     1.00     1.00\n"
        "layerwise         1        1.0        1.0        2.0"
        "     0.10     0.10     0.10\n"
        "(ratios vs the e2e trace — paper Table 3 comm column: "
        "layerwise 0.08, lw_fedssl 0.31, progressive 0.54)\n")


def test_fullscale_comm_matches_paper_column():
    """The abstract full-scale walk behind --paper-table reproduces the
    paper's comm multipliers to the printed precision."""
    e2e = trace_cli.fullscale_comm("e2e")
    for s in ("layerwise", "lw_fedssl", "progressive"):
        assert trace_cli.fullscale_comm(s) / e2e == pytest.approx(
            PAPER_MULT[s][2], abs=0.005), s


# ---------------------------------------------------------------------------
# provenance + resources bench schema
# ---------------------------------------------------------------------------
def test_provenance_header_validates():
    errs = []
    schemas._check_provenance({"provenance": provenance(seed=7)}, errs)
    assert errs == []
    errs = []
    schemas._check_provenance({}, errs)
    assert any("provenance" in e for e in errs)
    errs = []
    schemas._check_provenance(
        {"provenance": {"version": 1, "git_commit": 123}}, errs)
    assert any("git_commit" in e for e in errs)


def test_bench_validators_require_provenance():
    doc = {"bench": "simulation", "config": {}, "rows": [{}]}
    assert any("provenance" in e
               for e in schemas.validate_simulation_bench(doc))
    doc = {"bench": "privacy", "config": {}, "rows": [{}]}
    assert any("provenance" in e
               for e in schemas.validate_privacy_bench(doc))


def _resources_row(**over):
    row = {
        "engine": "sequential", "schedule": "e2e", "num_layers": 2,
        "batch_size": 4, "rounds": 2, "local_epochs": 1, "clients": 1,
        "stages": [{"sub_layers": 2, "active_from": 0, "align": False,
                    "depth_dropout": 0.0, "rounds": 2,
                    "flops_per_sample": 50.0,
                    "analytic_flops_per_sample": 50.0,
                    "analytic_memory_bytes": 1e6}],
        "flops_total": 100.0, "analytic_flops_total": 100.0,
        "analytic_peak_memory": 1e6, "program_peak_analytic": 1e6,
        "peak_memory": 1.5e6, "argument_bytes": 1e6,
        "output_bytes": 4e5, "temp_bytes": 1e5,
        "comm_bytes": 1000, "comm_ratio": 1.0,
        "analytic_flops_ratio": 1.0, "analytic_memory_ratio": 1.0,
        "flops_ratio": 1.0, "memory_ratio": 1.0,
    }
    row.update(over)
    return row


def _resources_doc(**over):
    return {"bench": "resources",
            "config": {"tolerances": {"flops_rtol": 0.30,
                                      "memory_factor": 3.0}},
            "rows": [_resources_row(**over)],
            "provenance": provenance(seed=0)}


def test_resources_bench_schema_enforces_tolerances():
    assert schemas.validate_resources_bench(_resources_doc()) == []
    # measured flops outside the documented rtol -> invalid document
    errs = schemas.validate_resources_bench(
        _resources_doc(flops_total=150.0))
    assert any("flops_total" in e and "outside" in e for e in errs)
    errs = schemas.validate_resources_bench(
        _resources_doc(peak_memory=9e6))
    assert any("peak_memory" in e and "outside" in e for e in errs)
    # flops-only documents (peak_memory null) are fine
    assert schemas.validate_resources_bench(_resources_doc(
        peak_memory=None, argument_bytes=None, output_bytes=None,
        temp_bytes=None, memory_ratio=None)) == []
    errs = schemas.validate_resources_bench(
        _resources_doc(unknown_field=1))
    assert any("unknown_field" in e for e in errs)


# ---------------------------------------------------------------------------
# regression gate: benchmarks.compare
# ---------------------------------------------------------------------------
def test_compare_passes_on_identical_docs():
    doc = _resources_doc()
    assert compare_mod.compare_docs("resources", doc, doc) == []


def test_compare_flags_metric_drift_and_row_coverage():
    base = _resources_doc()
    drifted = _resources_doc(flops_total=110.0)      # 10% > 5% rtol
    probs = compare_mod.compare_docs("resources", drifted, base)
    assert any("flops_total" in p and "drifted" in p for p in probs)
    # timing-free metrics within tolerance pass
    ok = _resources_doc(flops_total=101.0, peak_memory=1.6e6)
    assert compare_mod.compare_docs("resources", ok, base) == []
    # rows disappearing or appearing both gate
    two = dict(base, rows=base["rows"]
               + [_resources_row(schedule="layerwise")])
    assert any("coverage shrank" in p
               for p in compare_mod.compare_docs("resources", base, two))
    assert any("not in baseline" in p
               for p in compare_mod.compare_docs("resources", two, base))


def test_compare_nested_metric_paths():
    base = {"codecs": {"fp32": {"ratio": 1.0}, "int8": {"ratio": 4.0}}}
    vals = dict(compare_mod._lookup(base, "codecs.*.ratio"))
    assert vals == {"codecs.fp32.ratio": 1.0, "codecs.int8.ratio": 4.0}
    assert compare_mod._lookup({}, "codecs.*.ratio") \
        == [("codecs", KeyError)]


def test_compare_cli_exit_codes(tmp_path):
    r, b = tmp_path / "resources_bench.json", tmp_path / "base.json"
    b.write_text(json.dumps(_resources_doc()))
    r.write_text(json.dumps(_resources_doc()))
    assert compare_mod.main([str(r), str(b)]) == 0
    r.write_text(json.dumps(_resources_doc(flops_total=110.0)))
    assert compare_mod.main([str(r), str(b)]) == 1
    # directory mode: every baseline must have a results counterpart
    bdir = tmp_path / "baselines"
    bdir.mkdir()
    (bdir / "resources_bench.json").write_text(json.dumps(_resources_doc()))
    rdir = tmp_path / "results"
    rdir.mkdir()
    assert compare_mod.main(["--results-dir", str(rdir),
                             "--baselines-dir", str(bdir)]) == 1
    (rdir / "resources_bench.json").write_text(
        json.dumps(_resources_doc()))
    assert compare_mod.main(["--results-dir", str(rdir),
                             "--baselines-dir", str(bdir)]) == 0
    # schema-invalid results never pass the gate
    broken = _resources_doc()
    del broken["rows"][0]["comm_bytes"]
    (rdir / "resources_bench.json").write_text(json.dumps(broken))
    assert compare_mod.main(["--results-dir", str(rdir),
                             "--baselines-dir", str(bdir)]) == 1


# ---------------------------------------------------------------------------
# committed artifacts: results/ vs benchmarks/baselines/
# ---------------------------------------------------------------------------
def test_checked_in_resources_artifact_matches_baseline():
    res = ROOT / "results" / "resources_bench.json"
    base = ROOT / "benchmarks" / "baselines" / "resources_bench.json"
    if not res.exists() or not base.exists():
        pytest.skip("resources bench artifacts not generated yet")
    doc = json.loads(res.read_text())
    assert schemas.validate_resources_bench(doc) == []
    assert compare_mod.compare_files(res, base) == []
    rows = doc["rows"]
    assert {r["engine"] for r in rows} == {"sequential", "vmap"}
    assert len(rows) == 10                     # 5 schedules x 2 engines
    for r in rows:
        # acceptance: full-scale comm column matches the paper exactly
        assert r["comm_ratio"] == pytest.approx(
            PAPER_MULT[r["schedule"]][2], abs=0.005), r["schedule"]
        assert r["peak_memory"] is not None
