"""Device milliseconds per window round of the backward pass, remat
recompute included: op self time in the round program under any
``transpose(`` (``chipbench.scopes.READINGS``)."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("engine.backward_ms")
