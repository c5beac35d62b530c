"""95th percentile over the window's reactions of their pop-to-commit
wall time, ms: the harness's own timing of each reaction
(``Context.reactions``), read in the traced run, where the flight
recorder and the profiler are on. The untraced window's p95 rides the
reservation scan that grows with every job, and spreads between runs
too widely to bear a bound end to end; here it stays in view. Moves
reaction_p50_ms."""

import numpy as np


def read(ctx):
    reactions = getattr(ctx, "reactions", None)
    if not reactions:
        return None
    return float(np.percentile([(r.t1 - r.t0) * 1e3 for r in reactions], 95))
