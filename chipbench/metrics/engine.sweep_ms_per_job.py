"""``engine.sweep`` time per workload planned, ms: the uploads of a plan
pass's inputs, its jitted sweep and the fetch of its result. Moves
decisions_per_s."""


def read(ctx):
    spans = ctx.spans_named("engine.sweep")
    jobs = sum(s["args"].get("batch", 0) for s in spans)
    return sum(s["dur"] for s in spans) / 1e3 / jobs if jobs else None
