"""Faults planted in the program under test, for the readings that set
each limit's upper end and for the tests that see ``correct`` come out
false. Each replaces a function of the program for the duration of a
``with`` block.

``unchanged``   every step (the clients' local step and the server's
                calibration step) returns the state and optimizer state
                it was given (its loss is still reported);
``half_batch``  every step trains on the first half of its batch only;
``calibrate_once`` (LW-FedSSL) the server calibrates in round 0 only, a
                fault that round 0 cannot show and round 1 must.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch")
LATER_ROUND_FAULTS = ("calibrate_once",)


def _faulty(step, kind):
    if kind == "unchanged":
        def f(state, opt_state, images, *rest):
            _, _, metrics = step(state, opt_state, images, *rest)
            return state, opt_state, metrics
    else:
        def f(state, opt_state, images, *rest):
            return step(state, opt_state, images[:images.shape[0] // 2],
                        *rest)
    return f


def _once(calibrate):
    calls = []

    def f(state, *a, **kw):
        calls.append(1)
        return calibrate(state, *a, **kw) if len(calls) == 1 else state
    return f


@contextlib.contextmanager
def planted(kind):
    from repro.federated import client, server
    if kind not in FAULTS + LATER_ROUND_FAULTS:
        raise ValueError(f"no fault {kind!r}; one of "
                         f"{FAULTS + LATER_ROUND_FAULTS}")
    saved = [(m, n, getattr(m, n)) for m, n in (
        (client, "make_local_step"), (server, "make_calibration_step"),
        (server, "server_calibrate"))]
    if kind == "calibrate_once":
        server.server_calibrate = _once(server.server_calibrate)
    else:
        for m, n, make in saved[:2]:
            setattr(m, n, lambda *a, _make=make, **kw:
                    _faulty(_make(*a, **kw), kind))
    try:
        yield
    finally:
        for m, n, f in saved:
            setattr(m, n, f)
