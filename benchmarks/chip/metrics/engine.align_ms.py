"""Device milliseconds per window round of the alignment branch (the
frozen global encoder's forward, LW-FedSSL Eq. 3): op self time in the
round program under the scope ``align`` (``chipbench.scopes.READINGS``).
Nothing where the clients do not align."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("engine.align_ms")
