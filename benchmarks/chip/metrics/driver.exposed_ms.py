"""Host milliseconds per window round that the device waited on: device
idle time under the driver's host span ``round`` and its children,
outside ``engine.readback`` (``chipbench.scopes.ScopeTrace.exposed_ns``)."""
from chipbench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes, ctx.rounds).get("driver.exposed_ms")
