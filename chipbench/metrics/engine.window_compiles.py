"""Backend compiles and persistent-cache loads inside the window, from
``jax.monitoring``: a batch or refit size that set-up did not warm up.
Moves reaction_p95_ms."""


def read(ctx):
    return ctx.window_compiles
