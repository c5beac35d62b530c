"""Faults planted in the timed path, each one a way a later change could
break it: the tests see each judged not correct, and
``chipbench/control.py --fault <name>`` reads one on the chip. A fault
is installed on a freshly built world, before set-up replays its first
jobs."""

from __future__ import annotations


def answer_altered(world) -> None:
    """Every plan takes the grid point after the one the argmin chose,
    where the engine produces the plan."""
    eng = world.engine
    finish = eng._finish_plans
    g = eng._F.size

    def shifted(workloads, fits, objectives, flat, T64):
        return finish(workloads, fits, objectives, (flat + 1) % g, T64)

    eng._finish_plans = shifted


def half_the_batch(world) -> None:
    """Each characterization fit sees only the first half of its training
    samples."""
    from repro.core import svr

    fit_many = svr.fit_many

    def half(sets, **kw):
        return fit_many([(x[: len(x) // 2], y[: len(y) // 2]) for x, y in sets], **kw)

    svr.fit_many = half


def state_unchanged(world) -> None:
    """From the window on, a scheduling round returns without changing
    anything: no completion taken in, no job placed."""
    from repro.fleet.scheduler import RoundLog

    sched = world.sched
    step = sched.step
    warm = world.trace.warm_reactions

    def frozen(now):
        if len(sched.rounds) < warm:
            return step(now)
        return RoundLog(now=now, n_pending=0, planned=False)

    sched.step = frozen


def gram_in_bf16(world) -> None:
    """The fits' Gram built from bfloat16 points (one pass, as a float32
    matmul at default precision on a TPU)."""
    import jax.numpy as jnp
    from repro.core import svr

    def gram(x, y, gamma, impl):
        xb, yb = jnp.asarray(x, jnp.bfloat16), jnp.asarray(y, jnp.bfloat16)
        xy = jnp.einsum("bnd,bmd->bnm", xb, yb, preferred_element_type=jnp.float32)
        xx = jnp.sum(jnp.asarray(x) ** 2, -1)[..., :, None]
        yy = jnp.sum(jnp.asarray(y) ** 2, -1)[..., None, :]
        return jnp.exp(-gamma * jnp.maximum(xx + yy - 2 * xy, 0.0))

    svr._gram_batched = gram


def predict_in_bf16(world) -> None:
    """The surface predictions' matvec in one bfloat16 pass (a float32
    matmul at default precision on a TPU)."""
    import jax.numpy as jnp
    from repro.core import svr

    def from_gram(K, beta, bias, y_mean, y_std, log_target):
        ys = jnp.einsum("bmn,bn->bm", K.astype(jnp.bfloat16), beta.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        out = (ys + bias[:, None]) * y_std[:, None] + y_mean[:, None]
        return jnp.exp(out) if log_target else out

    svr._predict_from_gram = from_gram


# the module attributes of ``repro.core.svr`` that faults replace: restore
# them after a faulted run
PATCHED = ("fit_many", "_gram_batched", "_predict_from_gram")

FAULTS = {
    f.__name__: f
    for f in (answer_altered, half_the_batch, state_unchanged, gram_in_bf16, predict_in_bf16)
}
