"""Engine time per planned workload: the ``engine.plan_many`` spans over
the workloads they planned. Moves decisions_per_s."""


def read(ctx):
    spans = ctx.spans_named("engine.plan_many")
    jobs = sum(s["args"].get("batch", 0) for s in spans)
    if not jobs:
        return None
    return sum(s["dur"] for s in spans) / 1e3 / jobs
