"""Per-scope device time and program host spans (``chipbench.scopes``):
the protobuf reader on the recorded v5e traces, the op_name path rules,
and the reduction on a synthetic profile.

``data/scopes.xplane.pb.xz`` was recorded on a TPU v5e from the program
with its scopes and spans: the rehearsal cell of ``chipbench_tiny``
(2 blocks of d 32, layer-wise stage 2 with alignment, a cohort of 2 of
4 clients of 64 images at batch 16, calibration on 32 images), round 1
(one round program, two calibration steps) traced with the Python tracer
off. To fit a test file
it keeps what the reduction reads, each byte as recorded: the TPU plane's
``XLA Ops`` and ``XLA Modules`` lines (events without their stats) with
the event metadata's names and ``tf_op`` and ``program_id`` stats, and
the host plane's program-span events. Both reductions read it as they
read the whole trace, save the names of idle gaps."""
import lzma
import pathlib
from types import SimpleNamespace

import pytest

from chipbench import scopes, xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny.xplane.pb"
STEP = "jit(round_fn)/vmap()/while/body/closed_call/jit(step)"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(path of the recorded scoped trace, its ``ScopeTrace``)."""
    path = tmp_path_factory.mktemp("trace") / "scopes.xplane.pb"
    path.write_bytes(lzma.decompress(
        (DATA / "scopes.xplane.pb.xz").read_bytes()))
    return path, scopes.reduce_trace(path, 1)


def test_recorded_readings(recorded):
    """The six readings of one round of the recorded trace, in ms."""
    assert scopes.readings(recorded[1], rounds=1) == pytest.approx({
        "engine.frozen_ms": 0.048013, "engine.target_ms": 0.113003,
        "engine.align_ms": 0.114772, "engine.backward_ms": 0.159872,
        "engine.optimizer_ms": 0.016275, "driver.exposed_ms": 100.849157})


@pytest.mark.parametrize("name", sorted(scopes.READINGS)
                         + ["driver.exposed_ms"])
def test_each_scope_reader_reads_its_reading(recorded, name):
    """The per-layer metric's reader, as a traced run calls it, on the
    recorded trace; nothing where no trace was reduced."""
    from chipbench import harness
    read = harness.load_reader(name)
    want = scopes.readings(recorded[1], rounds=2)[name]
    assert read(SimpleNamespace(scopes=recorded[1], rounds=2)) == want
    assert read(SimpleNamespace(scopes=None, rounds=2)) is None


def test_recorded_scoped_shares(recorded):
    t = recorded[1]
    assert t.scoped_share() == pytest.approx(0.9063819833600458)
    assert t.scoped_share("jit_step") == pytest.approx(0.9684108949081652)
    # what carries no scope: the step scan's loop itself, the batch
    # gather, and compiler-inserted copies
    unscoped = sorted(((v, p) for p, v in t.op_ns["jit_round_fn"].items()
                       if not scopes.path_scopes(p)), reverse=True)
    assert [p for _, p in unscoped[:3]] == [
        "jit(round_fn)/vmap()/while/body/closed_call/gather",
        "jit(round_fn)/vmap()/while", ""]


def test_recorded_op_name_formats(recorded):
    """The formats the rules read, as the v5e trace has them."""
    paths = set(recorded[1].op_ns["jit_round_fn"])
    for want in [
            f"{STEP}/jvp(online)/frozen/while/body/closed_call/dot_general",
            f"{STEP}/jvp(target)/frozen/while/body/closed_call/checkpoint/"
            "dot_general",
            f"{STEP}/jvp(align)/trained/while/body/closed_call/checkpoint/"
            "dot_general",
            f"{STEP}/transpose(jvp(online))/trained/while/body/closed_call/"
            "checkpoint/rematted_computation/rsqrt",
            "jit(round_fn)/vmap()/while/body/closed_call/optimizer/"
            "jit(_where)/select_n",
            "jit(round_fn)/wire/vmap()/broadcast_in_dim",
            "jit(round_fn)/fedavg/reduce_sum"]:
        assert want in paths, want
    assert "jit(step)/calibrate/optimizer/add" in recorded[1].op_ns["jit_step"]


def test_recorded_host_spans(recorded):
    t = recorded[1]
    assert t.span_names() == {"round", "fl.sample", "download",
                              "wire.download", "local_train", "engine.plan",
                              "engine.dispatch", "engine.readback",
                              "calibrate"}
    assert len(t.spans) == 9             # one round


def test_recorded_trace_keeps_the_device_reduction(recorded):
    """The kept lines give the device reduction the module times it reads
    for ``engine.round_ms`` and ``server.calibrate_ms``."""
    d = xplane.reduce_trace(recorded[0], 1)
    assert d.module_ns["jit_round_fn"] == 1301140.0
    assert d.module_ns["jit_step"] == 385180.0
    assert d.busy_ns == 1843109.0


def test_tf_op_of_each_op_from_the_raw_trace():
    meta = scopes.op_metadata(TINY)
    assert list(meta) == ["/device:TPU:0"]
    ops = {name.split(" = ")[0]: tf_op
           for (_, name), tf_op in meta["/device:TPU:0"].items()}
    assert ops["%convolution_tanh_fusion"] == "jit(<lambda>)/dot_general:"
    assert ops["%multiply_reduce_fusion"] == "jit(<lambda>)/reduce_sum:"
    # compiler-inserted copies carry no tf_op and are left out
    assert not any(k.startswith("%copy") for k in ops)


def test_ops_join_their_module_and_metadata():
    t = scopes.reduce_trace(TINY, 1)
    assert set(t.op_ns) == {"jit__lambda"}
    paths = t.op_ns["jit__lambda"]
    assert paths["jit(<lambda>)/dot_general"] == 92178.0
    assert paths["jit(<lambda>)/reduce_sum"] == 82985.0
    assert paths[""] == 17814.0          # the copies: no op_name
    assert t.spans == []                 # recorded before the spans
    assert t.scoped_share("jit__lambda") == 0.0


# ---------------------------------------------------------------------------
# the wire-format reader on a hand-made message: interned strings
# ---------------------------------------------------------------------------
def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def test_reader_resolves_interned_tf_op(tmp_path):
    """A ``tf_op`` stat may hold its string as a reference to a stat
    metadata entry (``ref_value``) instead of inline (``str_value``)."""
    stat_meta = [(1, b"tf_op"), (2, b"program_id"),
                 (3, b"jit(round_fn)/jvp(online)/frozen/dot_general:")]
    plane = _msg(
        (2, b"/device:TPU:0"),
        (3, _msg((2, b"XLA Ops"))),                 # a line, skipped
        (4, _msg((1, 7), (2, _msg(
            (1, 7), (2, b"%fusion.1 = f32[8] fusion()"),
            (5, _msg((1, 1), (7, 3))),             # tf_op by reference
            (5, _msg((1, 2), (3, 42))))))),        # program_id
        (4, _msg((1, 8), (2, _msg(
            (1, 8), (2, b"%copy.2 = f32[8] copy()"),
            (5, _msg((1, 2), (3, 42))))))),        # no tf_op
        *((5, _msg((1, k), (2, _msg((1, k), (2, v))))) for k, v in stat_meta))
    host = _msg((2, b"/host:CPU"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, plane), (1, host)))
    assert scopes.op_metadata(path) == {"/device:TPU:0": {
        (42, "%fusion.1 = f32[8] fusion()"):
            "jit(round_fn)/jvp(online)/frozen/dot_general:"}}


# ---------------------------------------------------------------------------
# op_name paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path,want,backward", [
    (f"{STEP}/jvp(online)/frozen/while/body/closed_call/dot_general",
     {"online", "frozen"}, False),
    (f"{STEP}/transpose(jvp(online))/trained/while/body/closed_call/"
     "checkpoint/rematted_computation/mul", {"online", "trained"}, True),
    (f"{STEP}/jvp(target)/heads/dot_general", {"target", "heads"}, False),
    (f"{STEP}/transpose(jvp(loss))/jit(norm)/mul", {"loss"}, True),
    (f"{STEP}/augment/jit(two_views)/vmap()/gather", {"augment"}, False),
    ("jit(round_fn)/vmap()/while/body/closed_call/optimizer/select_n",
     {"optimizer"}, False),
    ("jit(round_fn)/wire/vmap()/scatter", {"wire"}, False),
    ("jit(step)/calibrate/optimizer/add", {"calibrate", "optimizer"},
     False),
    # a function named like a scope is no scope
    (f"{STEP}/jit(loss)/add", set(), False),
    (f"{STEP}/vmap(jit(_threefry_split))/slice", set(), False),
])
def test_path_scopes(path, want, backward):
    assert scopes.path_scopes(path) == want
    assert scopes.in_backward(path) == backward


def test_op_path_strips_the_type_and_merged_names():
    assert scopes.op_path("jit(f)/jvp(align)/add:") == "jit(f)/jvp(align)/add"
    assert scopes.op_path("jit(f)/a/b;jit(f)/c/b:") == "jit(f)/a/b"
    assert scopes.op_path("") == ""


# ---------------------------------------------------------------------------
# the reduction on a synthetic profile
# ---------------------------------------------------------------------------
def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=float(start),
                           duration_ns=float(dur))


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


@pytest.fixture
def synthetic():
    """One round program (id 1) of four ops, a while loop around two,
    then a calibration program (id 2); host spans around and between."""
    ops = [_ev("while", 0, 100), _ev("f1", 10, 30), _ev("f2", 50, 40),
           _ev("sel", 120, 30), _ev("g", 200, 50)]
    mods = [_ev("jit_round_fn(1)", 0, 150), _ev("jit_step(2)", 200, 50)]
    meta = {"/device:TPU:0": {
        (1, "while"): "jit(round_fn)/vmap()/while:",
        (1, "f1"): "jit(round_fn)/jvp(online)/frozen/dot_general:",
        (1, "f2"): "jit(round_fn)/transpose(jvp(online))/trained/mul:",
        (1, "sel"): "jit(round_fn)/optimizer/select_n:",
        (2, "g"): "jit(step)/calibrate/jvp(target)/frozen/add:"}}
    host = [_ev("round", 140, 200), _ev("engine.readback", 145, 10),
            _ev("fl.sample", 160, 30), _ev("PjRtExecute", 190, 5),
            _ev("round", 400, 10)]
    planes = [_plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods),
              SimpleNamespace(name="/host:CPU", lines=[
                  SimpleNamespace(name="python", events=host)])]
    return scopes.reduce_profile(planes, 1, meta)


def test_reduce_profile_joins_ops_by_module(synthetic):
    assert synthetic.op_ns == {
        "jit_round_fn": {"jit(round_fn)/vmap()/while": 30.0,
                         "jit(round_fn)/jvp(online)/frozen/dot_general": 30.0,
                         "jit(round_fn)/transpose(jvp(online))/trained/mul":
                             40.0,
                         "jit(round_fn)/optimizer/select_n": 30.0},
        "jit_step": {"jit(step)/calibrate/jvp(target)/frozen/add": 50.0}}
    assert synthetic.idle == [[(100.0, 120.0), (150.0, 200.0)]]
    assert synthetic.span_names() == {"round", "engine.readback",
                                      "fl.sample"}
    assert synthetic.scoped_share() == pytest.approx(100 / 130)


def test_exposed_time_leaves_out_the_readback(synthetic):
    # idle 150-200: round covers it all, the readback 150-155 of it
    assert synthetic.exposed_ns() == 45.0


def test_readings_per_round(synthetic):
    r = scopes.readings(synthetic, rounds=2)
    assert r == pytest.approx({
        "engine.frozen_ms": 15e-6, "engine.backward_ms": 20e-6,
        "engine.optimizer_ms": 15e-6, "driver.exposed_ms": 22.5e-6})
    # the calibration's target branch is not the round program's
    assert "engine.target_ms" not in r and "engine.align_ms" not in r


def test_readings_of_a_program_without_scopes_or_spans():
    """The parent program has neither: every reading is left out."""
    assert scopes.readings(scopes.reduce_trace(TINY, 1), rounds=2) == {}
    assert scopes.readings(None, rounds=2) == {}


def test_overlap():
    assert scopes._overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert scopes._overlap([(0, 10)], []) == 0
    assert scopes._overlap([(0, 10)], [(0, 2), (4, 6), (8, 12)]) == 6
