"""Programs compiled or loaded from the persistent cache inside the
window, from JAX's ``/jax/core/compile/backend_compile_duration`` event.
Set-up warms every program the window runs, so this reads 0."""


def read(ctx):
    return ctx.compiles
