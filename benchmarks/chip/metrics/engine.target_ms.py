"""Device milliseconds per window round of the target branch's forward:
op self time in the round program under the scope ``target``
(``chipbench.scopes.READINGS``)."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("engine.target_ms")
