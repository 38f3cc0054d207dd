"""Device milliseconds per window round of the frozen prefix's forward,
one copy: the online encoder's blocks below the stage, run once per view
and shared by every branch. Op self time in the round program under the
scopes ``online`` and ``frozen``, outside the backward pass
(``chipbench.scopes.READINGS``). Nothing at e2e, where no prefix is
frozen."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("engine.frozen_ms")
