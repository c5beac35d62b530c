"""Backend compiles and persistent-cache loads in the window, as the
program counts them (``jax.compiles``; each also a ``jax.compile`` span
inside the engine span that caused it). Moves reaction_p95_ms."""


def read(ctx):
    return ctx.counter("jax.compiles")
