"""The trace reduction on a small trace recorded on a TPU v5e: three
calls each of two jitted programs with host sleeps between them."""
import pathlib

import pytest

from chipbench import xplane

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce_trace(TRACE, 1)


def test_busy_time_is_the_union_of_op_intervals(trace):
    assert trace.busy_ns == 192977.0
    assert trace.busy_s == pytest.approx(192977e-9)


def test_per_module_time(trace):
    assert trace.module_ns == {"jit__lambda": 193018.0}
    assert trace.module_s(r"^jit__lambda$") == pytest.approx(193018e-9)
    assert trace.module_s(r"^jit_round_fn$") == 0


def test_op_self_times_sum_to_the_busy_time(trace):
    assert sum(trace.op_ns.values()) == pytest.approx(trace.busy_ns,
                                                      rel=1e-3)
    top = trace.breakdown()["device_ops"]
    assert top[0][0] == "multiply_reduce_fusion"
    assert len(top) <= xplane.TOP


def test_idle_gaps_are_named_by_the_host(trace):
    gaps = trace.breakdown()["idle_gaps"]
    assert gaps[0][0] == "$time sleep"
    assert gaps[0][1] == pytest.approx(0.006915596)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_helpers():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.op_name("%while.3 = (s32[]) while(...)") == "while.3"
    assert xplane.module_name("jit_step(123)") == "jit_step"
    times = xplane.self_times([(0, 10, "while"), (1, 4, "a"), (5, 7, "b"),
                               (12, 13, "a")])
    assert times == {"while": 5, "a": 4, "b": 2}
