"""``fleet.candidates`` time per ``fleet.place`` span, ms: the capacity
checks and point projections over the pool that choose each job's node.
Moves reaction_p50_ms."""


def read(ctx):
    cand = ctx.spans_named("fleet.candidates")
    places = ctx.spans_named("fleet.place")
    if not cand or not places:
        return None
    return sum(s["dur"] for s in cand) / 1e3 / len(places)
