"""Backend compiles and persistent-cache loads in the window, as the
program counts them (``jax.compiles``; each also a ``jax.compile`` span
inside the engine span that caused it). A compile in the window holds
the loop for its seconds. Moves decisions_per_s."""


def read(ctx):
    return ctx.counter("jax.compiles")
