"""Device milliseconds per window round of server calibration
(``repro.federated.server.make_calibration_step``): the XLA modules of
the jitted ``step``. In a vmap cell no other top-level program is named
``step`` (the clients' local step is traced inside ``round_fn``)."""


def read(ctx):
    if ctx.trace is None or not ctx.rounds:
        return None
    s = ctx.trace.module_s(r"^jit_step$")
    return 1e3 * s / ctx.rounds if s > 0 else None
