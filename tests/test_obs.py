"""Observability subsystem (repro.obs): span tracer semantics and
determinism, bit-identity of traced vs untraced training on both engines,
exporter schemas (JSONL / Chrome trace_event / metrics CSV), FLHistory's
versioned JSON round-trip, and the trace CLI's reproduction of the
paper's per-schedule comm ratios from traces alone.
"""
import io
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import schemas
from repro.configs.base import FLConfig, ModelConfig, SSLConfig, TrainConfig
from repro.data import iid_partition, synthetic_images
from repro.federated import simulation as sim_mod
from repro.federated.driver import FLHistory, HISTORY_VERSION, run_fedssl
from repro.launch import trace as trace_cli
from repro.obs import (NOOP_OBS, ConsoleRenderer, chrome_trace_doc,
                       format_round_line, make_obs, metrics_csv_text,
                       read_jsonl, write_chrome_trace, write_jsonl)
from repro.obs.core import Observability
from repro.obs.metrics import NOOP_METRICS, MetricsRegistry
from repro.obs.trace import NOOP_TRACER, Tracer, is_tracing
from repro.core import schedule as sched
from repro.core import ssl as ssl_mod
from repro.federated import engine as engine_mod
from repro.federated import server
from repro.optim import make_optimizer

CFG = ModelConfig("t-vit", "dense", 2, 32, 2, 2, 64, 0, causal=False,
                  compute_dtype="float32", act="gelu")
SSLC = SSLConfig(proj_hidden=32, pred_hidden=32, proj_dim=16)
TC = TrainConfig(batch_size=16, base_lr=1.5e-4)

# paper Table 3 comm multipliers vs FedMoCo (e2e); tolerance matches
# tests/test_federated.py's analytic-cost check
PAPER_COMM = {"e2e": 1.00, "layerwise": 0.08, "lw_fedssl": 0.31,
              "progressive": 0.54, "fll_dd": 0.08}


def _run(engine="sequential", obs=None, rounds=2, schedule="lw_fedssl",
         sim=None, seed=0):
    key = jax.random.PRNGKey(seed)
    imgs, _ = synthetic_images(key, 96, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(96, 3)]
    fl = FLConfig(num_clients=3, rounds=rounds, local_epochs=1,
                  schedule=schedule, server_epochs=1)
    return run_fedssl(CFG, SSLC, fl, TC, images=imgs, client_indices=idx,
                      aux_images=imgs[:16], key=key, engine=engine,
                      sim=sim, obs=obs)


@pytest.fixture(scope="module")
def traced_run():
    """One traced+metered run shared by the exporter/schema tests."""
    obs = make_obs(trace=True, metrics=True, mode="test")
    state, hist = _run(obs=obs)
    return obs, state, hist


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------
def test_span_nesting_and_attrs():
    t = Tracer()
    with t.span("run", cat="fl", mode="x"):
        with t.span("round", cat="fl", round=0) as r:
            with t.span("download", cat="fl"):
                pass
            r.set(loss=1.5)
        t.instant("marker", cat="fl", stage=2)
    names = [e["name"] for e in t.events]
    # children close before parents -> appear first in the event stream
    assert names == ["download", "round", "marker", "run"]
    by_name = {e["name"]: e for e in t.events}
    assert by_name["round"]["parent"] == by_name["run"]["seq"]
    assert by_name["download"]["parent"] == by_name["round"]["seq"]
    assert by_name["download"]["depth"] == 2      # run=0, round=1
    assert by_name["round"]["args"] == {"round": 0, "loss": 1.5}
    assert by_name["marker"]["ph"] == "i"
    assert by_name["marker"]["parent"] == by_name["run"]["seq"]
    for e in t.events:
        assert e["dur"] >= 0.0


def test_virtual_tracks_get_distinct_tids():
    t = Tracer()
    t.virtual_span("c0 r0", "sim client 0", 0.0, 1.0, client=0)
    t.virtual_span("c1 r0", "sim client 1", 0.0, 2.0, client=1)
    t.virtual_span("c0 r1", "sim client 0", 1.0, 1.0, client=0)
    tids = {e["tid"] for e in t.events}
    assert len(tids) == 2 and 0 not in tids       # 0 is the main track
    assert t.tracks["sim client 0"] != t.tracks["sim client 1"]
    # caller-supplied virtual timestamps, in microseconds
    assert t.events[2]["ts"] == pytest.approx(1e6)
    assert t.events[1]["dur"] == pytest.approx(2e6)


def test_noop_surfaces_do_nothing():
    assert not is_tracing(NOOP_TRACER)
    with NOOP_TRACER.span("x") as sp:
        sp.set(a=1)
    NOOP_TRACER.instant("y")
    NOOP_TRACER.virtual_span("z", "trk", 0.0, 1.0)
    assert NOOP_TRACER.events == [] and NOOP_TRACER.structure() == []
    NOOP_METRICS.counter("c").inc()
    NOOP_METRICS.gauge("g").set(3)
    NOOP_METRICS.histogram("h").observe(1.0)
    assert not NOOP_OBS.enabled
    assert NOOP_OBS.export(trace_jsonl="/nonexistent/x.jsonl") == {}


def test_spans_reach_the_profiler_only_while_it_records(tmp_path):
    """Both tracers' spans enter a ``TraceAnnotation`` while a profile
    records, with their attributes; the no-op tracer keeps its
    allocation-free singleton otherwise."""
    assert NOOP_TRACER.span("x") is NOOP_TRACER.span("y")
    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with NOOP_TRACER.span("noop.span", n=1) as sp:
            sp.set(late=2)
        with t.span("tracer.span", cat="fl", n=3) as sp:
            sp.set(late=4)
    finally:
        jax.profiler.stop_trace()
    assert NOOP_TRACER.events == []
    assert [e["name"] for e in t.events] == ["tracer.span"]
    assert t.events[0]["args"] == {"n": 3, "late": 4}
    events = {ev.name: dict(ev.stats) for pl, ev in _host_events(tmp_path)}
    assert events["noop.span"] == {"n": 1, "late": 2}
    assert events["tracer.span"] == {"n": 3, "late": 4}


def test_make_obs_enablement():
    assert not make_obs().enabled
    assert make_obs(trace=True).enabled
    assert make_obs(metrics=True).enabled
    o = make_obs(trace=True, run="r1")
    assert is_tracing(o.tracer) and o.tracer.meta["run"] == "r1"
    assert isinstance(Observability(), type(NOOP_OBS))


# ---------------------------------------------------------------------------
# driver integration: determinism + bit-identity
# ---------------------------------------------------------------------------
def test_trace_structure_deterministic_across_runs():
    """Same seed -> identical timestamp-free span structure (ordering,
    nesting, names and attrs), on both engines."""
    for engine in ("sequential", "vmap"):
        o1, o2 = (make_obs(trace=True) for _ in range(2))
        _run(engine=engine, obs=o1)
        _run(engine=engine, obs=o2)
        s1, s2 = o1.tracer.structure(), o2.tracer.structure()
        assert s1 == s2
        assert any(ev[3] == "round" for ev in s1)


@pytest.mark.parametrize("engine", ["sequential", "vmap"])
def test_observability_is_bit_identical(engine):
    """Tracing+metrics is host-side only: the trained fp32 state must be
    byte-identical with obs fully enabled, no-op, and absent."""
    s_off, h_off = _run(engine=engine, obs=None)
    s_noop, _ = _run(engine=engine, obs=NOOP_OBS)
    s_on, h_on = _run(engine=engine,
                      obs=make_obs(trace=True, metrics=True))
    for a, b, c in zip(jax.tree.leaves(s_off["online"]),
                       jax.tree.leaves(s_noop["online"]),
                       jax.tree.leaves(s_on["online"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))
    assert h_off.loss == h_on.loss


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    for pl in ProfileData.from_file(str(path)).planes:
        if pl.name == "/host:CPU":
            for ln in pl.lines:
                for ev in ln.events:
                    yield pl, ev


def _profiled_run(trace_dir, **kw):
    """``_run`` under ``jax.profiler``, Python tracer off (it would only
    slow the test)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return _run(schedule="e2e", **kw)
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("engine", ["sequential", "vmap"])
def test_profiled_training_is_bit_identical(tmp_path, engine):
    """Spans in the profiler's trace are host-side only: a run recorded
    by ``jax.profiler`` trains byte-identically to one that is not."""
    s_off, h_off = _run(engine=engine, obs=None, schedule="e2e")
    s_on, h_on = _profiled_run(tmp_path, engine=engine, obs=None)
    for a, b in zip(jax.tree.leaves(s_off["online"]),
                    jax.tree.leaves(s_on["online"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert h_off.loss == h_on.loss


@pytest.mark.parametrize("traced", [False, True])
def test_vmap_round_spans_in_the_profiler_trace(tmp_path, traced):
    """One span set, two sinks: a 2-round vmap run under ``jax.profiler``
    puts the driver's and the engine's spans on the trace's host plane,
    with or without the in-memory tracer, which records the same names."""
    obs = make_obs(trace=True) if traced else None
    _profiled_run(tmp_path, engine="vmap", obs=obs)
    names = [ev.name for _, ev in _host_events(tmp_path)]
    want = ["round", "fl.sample", "engine.plan", "engine.dispatch",
            "engine.readback"]
    for name in want:
        assert names.count(name) == 2, name
    if traced:
        recorded = [e["name"] for e in obs.tracer.events]
        assert all(recorded.count(name) == 2 for name in want)


# ---------------------------------------------------------------------------
# device scopes in the programs' op_name metadata
# ---------------------------------------------------------------------------
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def _op_paths(hlo_text):
    """Each op location of the lowered program as the set of its path
    components, transforms stripped (``transpose(jvp(online))`` ->
    ``online``)."""
    out = []
    for loc in re.findall(r'loc\("([^"]*)"', hlo_text):
        parts = set()
        for part in loc.split("/"):
            while (m := _TRANSFORM.match(part)):
                part = m.group(1)
            parts.add(part)
        out.append(parts)
    return out


def _scopes(hlo_text):
    """The scope-like path components of the lowered program's op
    locations."""
    return set().union(*_op_paths(hlo_text))


def _stage2_programs():
    key = jax.random.PRNGKey(0)
    imgs, _ = synthetic_images(key, 96, 10, 32)
    idx = [jnp.asarray(i) for i in iid_partition(96, 3)]
    fl = FLConfig(num_clients=3, rounds=2, local_epochs=1,
                  schedule="lw_fedssl", server_epochs=1)
    encoder = ssl_mod.make_vit_encoder(CFG, 32)
    opt = make_optimizer(TC)
    eng = engine_mod.make_engine(
        "vmap", encoder=encoder, ssl_cfg=SSLC, opt=opt, fl=fl,
        train_cfg=TC, images=imgs, client_indices=idx)
    plan = next(p for p in sched.build_schedule(fl, encoder.num_stages)
                if p.active_from > 0 and p.align)
    round_text = eng.lower_round(plan, clients=2).as_text(debug_info=True)
    state = jax.eval_shape(lambda k: ssl_mod.ssl_init(k, encoder, SSLC),
                           key)
    step = server.make_calibration_step(encoder, SSLC, opt,
                                        sub_layers=plan.sub_layers)
    calib_text = step.lower(
        state, jax.eval_shape(opt.init, state["online"]),
        jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.float32)).as_text(debug_info=True)
    return round_text, calib_text


@pytest.fixture(scope="module")
def stage2_programs():
    return _stage2_programs()


@pytest.fixture(scope="module")
def stage2_scopes(stage2_programs):
    return [_scopes(t) for t in stage2_programs]


@pytest.mark.parametrize("scope", [
    "online", "target", "align", "frozen", "trained", "heads", "loss",
    "augment", "optimizer", "wire", "fedavg"])
def test_round_program_carries_scope(stage2_scopes, scope):
    """The vmap round program of a layer-wise stage with alignment names
    each branch of the client step and the round's wire and FedAvg
    parts; the profiler's trace reads them back per op."""
    assert scope in stage2_scopes[0]


@pytest.mark.parametrize("scope", [
    "calibrate", "augment", "online", "target", "frozen", "trained",
    "heads", "loss", "optimizer"])
def test_calibration_step_carries_scope(stage2_scopes, scope):
    assert scope in stage2_scopes[1]


def test_round_program_computes_frozen_prefix_once(stage2_programs):
    """At a layer-wise stage with alignment the frozen prefix runs only
    under ``online``: the target and alignment branches start from it, so
    no op of theirs is a frozen block. Server calibration trains end to
    end, shares nothing, and its target still runs every block frozen."""
    round_paths, calib_paths = (_op_paths(t) for t in stage2_programs)
    assert any({"online", "frozen"} <= p for p in round_paths)
    assert not [p for p in round_paths
                if "frozen" in p and p & {"target", "align"}]
    assert any({"target", "trained"} <= p for p in round_paths)
    assert any({"align", "trained"} <= p for p in round_paths)
    assert any({"target", "frozen"} <= p for p in calib_paths)


def test_metrics_agree_with_history(traced_run):
    obs, _, hist = traced_run
    d = obs.metrics.to_dict()
    assert d["counters"]["fl.rounds"] == len(hist.loss)
    assert d["counters"]["comm.download_bytes"] == sum(hist.download_bytes)
    assert d["counters"]["wire.upload_bytes"] == sum(hist.wire_upload_bytes)
    assert d["counters"]["jit.recompiles"] > 0          # first round compiles
    assert d["histograms"]["round.loss"]["count"] == len(hist.loss)
    assert d["gauges"]["wire.compression_ratio"] == pytest.approx(
        hist.compression_ratio)


def test_round_span_bytes_match_history(traced_run):
    obs, _, hist = traced_run
    rounds = [e for e in obs.tracer.events if e["name"] == "round"]
    rounds.sort(key=lambda e: e["args"]["round"])
    assert [e["args"]["download_bytes"] for e in rounds] \
        == hist.download_bytes
    assert [e["args"]["wire_upload_bytes"] for e in rounds] \
        == hist.wire_upload_bytes
    # fp32 identity codec: wire == analytic, per round
    assert [e["args"]["wire_download_bytes"] for e in rounds] \
        == hist.download_bytes


def test_simulation_emits_virtual_client_tracks():
    sim = sim_mod.make_sim("uniform", "synchronous", num_clients=3, seed=0)
    obs = make_obs(trace=True)
    _run(obs=obs, sim=sim)
    tracks = obs.tracer.tracks
    assert any(name.startswith("sim client") for name in tracks)
    virt = [e for e in obs.tracer.events if e["cat"] == "sim"
            and e["ph"] == "X"]
    assert virt and all("energy_j" in e["args"] for e in virt)
    assert any(e["name"].startswith("policy.") for e in obs.tracer.events
               if e["ph"] == "i")


# ---------------------------------------------------------------------------
# exporters + schemas
# ---------------------------------------------------------------------------
def test_jsonl_roundtrip_and_schema(tmp_path, traced_run):
    obs, _, _ = traced_run
    p = write_jsonl(obs.tracer, tmp_path / "t.jsonl", schedule="lw_fedssl")
    header, events = read_jsonl(p)
    assert schemas.validate_trace_jsonl(header, events) == []
    assert header["schedule"] == "lw_fedssl"
    assert events == obs.tracer.events
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "something-else"}\n')
        read_jsonl(bad)


def test_chrome_trace_schema(tmp_path, traced_run):
    obs, _, _ = traced_run
    doc = chrome_trace_doc(obs.tracer)
    assert schemas.validate_chrome_trace(doc) == []
    p = write_chrome_trace(obs.tracer, tmp_path / "t.chrome.json")
    assert schemas.validate_chrome_trace(json.loads(p.read_text())) == []
    # the validator actually catches malformed documents
    assert schemas.validate_chrome_trace({}) != []
    assert schemas.validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "n", "cat": "c", "ts": 0,
                          "pid": 0, "tid": 0, "args": {}}],
         "displayTimeUnit": "ms"}) != []          # X without dur
    assert schemas.validate_chrome_trace(
        {"traceEvents": [{"ph": "i", "name": "n", "cat": "c", "ts": 0,
                          "pid": 0, "tid": 0, "args": {}}],
         "displayTimeUnit": "ms"}) != []          # instant without scope


def test_metrics_csv_schema(traced_run):
    obs, _, _ = traced_run
    text = metrics_csv_text(obs.metrics)
    assert schemas.validate_metrics_csv(text) == []
    assert schemas.validate_metrics_csv("not,a,header\n") != []
    assert schemas.validate_metrics_csv(
        "metric,type,field,value\nm,counter,oops,1\n") != []
    assert schemas.validate_metrics_csv(
        "metric,type,field,value\nm,counter,value,NaNope\n") != []


def test_obs_export_writes_requested_artifacts(tmp_path, traced_run):
    obs, _, _ = traced_run
    written = obs.export(trace_jsonl=tmp_path / "a.jsonl",
                         chrome_trace=tmp_path / "a.chrome.json",
                         metrics_csv=tmp_path / "a.csv")
    assert set(written) == {"trace_jsonl", "chrome_trace", "metrics_csv"}
    for p in written.values():
        assert p.exists() and p.stat().st_size > 0


# ---------------------------------------------------------------------------
# console renderer (shared round-line formatter)
# ---------------------------------------------------------------------------
def test_format_round_line():
    line = format_round_line(0, 12, 1, 5.1234, lr=1.5e-4, down_mb=0.5,
                             up_mb=0.25, wire_mb=0.75)
    assert line == ("round 1/12 stage 1 loss 5.1234 lr 1.50e-04 "
                    "down 0.50MB up 0.25MB wire 0.75MB")
    assert format_round_line(2, 4, 2, 1.0) == "round 3/4 stage 2 loss 1.0000"


def test_console_renderer_modes():
    buf = io.StringIO()
    r = ConsoleRenderer(stream=buf)
    r("one"); r("two"); r.close()
    assert buf.getvalue() == "one\ntwo\n"
    buf = io.StringIO()
    with ConsoleRenderer(live=True, stream=buf) as r:
        r("a long status line")
        r("short")
    out = buf.getvalue()
    assert out.startswith("\ra long status line\rshort")
    assert out.endswith("\n")                     # close() terminates
    # the shorter line is padded over the longer one
    assert len(out.split("\r")[2]) >= len("a long status line")


# ---------------------------------------------------------------------------
# FLHistory round-trip + NaN regression
# ---------------------------------------------------------------------------
def test_history_empty_compression_ratio_is_nan():
    assert math.isnan(FLHistory().compression_ratio)


def test_history_json_roundtrip():
    h = FLHistory(loss=[2.0, 1.5], round_stage=[1, 2],
                  download_bytes=[10, 20], upload_bytes=[10, 20],
                  wire_download_bytes=[5, 10], wire_upload_bytes=[5, 10],
                  round_wall_clock=[1.0, 2.0], device_seconds=[2.0, 4.0],
                  energy_joules=[0.5, 0.6], dropped_clients=[0, 1],
                  participants=[(0, 1), (1, 2)])
    d = json.loads(json.dumps(h.to_dict()))
    assert d["version"] == HISTORY_VERSION
    h2 = FLHistory.from_dict(d)
    assert h2 == h
    assert h2.participants == [(0, 1), (1, 2)]    # tuples restored
    assert h2.compression_ratio == pytest.approx(2.0)
    with pytest.raises(ValueError):
        FLHistory.from_dict({"version": 999, "fields": {}})
    with pytest.raises(ValueError):
        FLHistory.from_dict({"version": HISTORY_VERSION,
                             "fields": {"nope": []}})


def test_traced_history_roundtrips(traced_run):
    _, _, hist = traced_run
    assert FLHistory.from_dict(
        json.loads(json.dumps(hist.to_dict()))) == hist


# ---------------------------------------------------------------------------
# trace CLI: the paper's comm table from traces alone
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_comm_dryrun_traces_reproduce_paper_ratios(tmp_path):
    """--emit-comm walks the full 180-round vit-tiny schedules through the
    real Transport accounting; the analysis CLI's comm table must land on
    the paper's per-schedule upload/download multipliers, and fp32 wire
    bytes must equal comm.round_comm_bytes exactly in every round."""
    traces = []
    for s in PAPER_COMM:
        p = trace_cli.emit_comm_trace(s, tmp_path / f"{s}.jsonl")
        header, events = read_jsonl(p)
        assert schemas.validate_trace_jsonl(header, events) == []
        for e in trace_cli.round_spans(events):    # fp32: wire == analytic
            assert e["args"]["wire_download_bytes"] \
                == e["args"]["download_bytes"]
            assert e["args"]["wire_upload_bytes"] \
                == e["args"]["upload_bytes"]
        traces.append((header, events))
    rows = {r["schedule"]: r for r in trace_cli.comm_table(traces)}
    for s, want in PAPER_COMM.items():
        assert rows[s]["rounds"] == 180
        assert rows[s]["comm_ratio"] == pytest.approx(want, abs=0.06), s


def test_trace_cli_analyzes_live_trace(tmp_path, capsys, traced_run):
    obs, _, _ = traced_run
    p = write_jsonl(obs.tracer, tmp_path / "run.jsonl")
    trace_cli.main([str(p)])
    out = capsys.readouterr().out
    assert "comm totals" in out and "lw_fedssl" in out
    assert "round" in out                          # breakdown table
