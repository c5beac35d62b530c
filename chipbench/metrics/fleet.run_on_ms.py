"""Mean ``fleet.run_on`` span, ms: the node model's run of one placement
(``FleetScheduler._launch``), one per launch. Moves reaction_p50_ms."""

from chipbench.spans import mean


def read(ctx):
    return mean([s["dur"] / 1e3 for s in ctx.spans_named("fleet.run_on")])
