"""Bytes the engine hands to the device and reads back per workload
planned: (``engine.h2d_bytes`` + ``engine.d2h_bytes``) over the
workloads of the ``engine.sweep`` spans. At B = 1, G = 352: T 1,408 + W
1,408 + k 4 + mask 352 up, one int32 index down, 3,176. Moves
decisions_per_s."""


def read(ctx):
    h2d, d2h = ctx.counter("engine.h2d_bytes"), ctx.counter("engine.d2h_bytes")
    jobs = sum(s["args"].get("batch", 0) for s in ctx.spans_named("engine.sweep"))
    if h2d is None or d2h is None or not jobs:
        return None
    return (h2d + d2h) / jobs
