"""Device milliseconds per window round of the host-called broadcast
(``repro.federated.transport.Transport.broadcast``, XLA wire path): the
jitted pack/codec/unpack program ``fn``. The upload path runs inside the
round program and is counted in ``engine.round_ms``."""


def read(ctx):
    if ctx.trace is None or not ctx.rounds:
        return None
    s = ctx.trace.module_s(r"^jit_fn$")
    return 1e3 * s / ctx.rounds if s > 0 else None
