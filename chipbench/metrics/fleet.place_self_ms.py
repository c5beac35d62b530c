"""Mean, over planning rounds, of the ``fleet.place`` span less the engine
pass inside it: choosing the node and launching, host work of every
placement. Moves reaction_p50_ms."""

from chipbench.spans import mean, self_times_ms


def read(ctx):
    engine = ctx.spans_named("engine.plan_many")
    return mean(self_times_ms(ctx.spans_named("fleet.place"), engine))
