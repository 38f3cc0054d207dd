"""Device milliseconds per window round of the clients' optimizer (the
update, the target's EMA and the padded-step select): op self time in
the round program under the scope ``optimizer``
(``chipbench.scopes.READINGS``)."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("engine.optimizer_ms")
