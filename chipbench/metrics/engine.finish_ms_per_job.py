"""``engine.finish_plans`` time per workload planned, ms: building the
plans from the chosen grid indices on the host. Moves decisions_per_s."""


def read(ctx):
    spans = ctx.spans_named("engine.finish_plans")
    jobs = sum(s["args"].get("batch", 0) for s in spans)
    return sum(s["dur"] for s in spans) / 1e3 / jobs if jobs else None
