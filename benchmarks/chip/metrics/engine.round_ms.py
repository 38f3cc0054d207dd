"""Device milliseconds per window round of the vmap engine's round
program: every client's local steps, the in-program upload wire path and
FedAvg, one XLA module named after ``round_fn``
(``repro.federated.engine.build_round_program``)."""


def read(ctx):
    if ctx.trace is None or not ctx.rounds:
        return None
    s = ctx.trace.module_s(r"^jit_round_fn$")
    return 1e3 * s / ctx.rounds if s > 0 else None
