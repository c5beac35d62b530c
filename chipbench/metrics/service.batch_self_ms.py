"""Mean self time of a reaction's ``service.batch`` span, its
``fleet.round`` child taken out: the service's own work per event batch
(applying events, bookkeeping). Moves reaction_p50_ms."""

from chipbench.spans import mean, self_times_ms


def read(ctx):
    return mean(self_times_ms(ctx.spans_named("service.batch"), ctx.spans_named("fleet.round")))
