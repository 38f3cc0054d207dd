"""Share of the window in which no operation ran on the device: one minus
the union of the device's XLA op intervals over the window's seconds, in
percent."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.window_s)
