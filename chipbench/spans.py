"""Span arithmetic for the per-layer readers: flight-recorder spans are
dicts with ``ts`` and ``dur`` in microseconds, recorded on one thread, so
they nest."""

from __future__ import annotations

from typing import List


def self_times_ms(parents: List[dict], children: List[dict]) -> List[float]:
    """Each parent's duration less the children that lie inside it, ms."""
    kids = sorted((c["ts"], c["ts"] + c["dur"]) for c in children)
    out, j = [], 0
    for p in sorted(parents, key=lambda s: s["ts"]):
        lo, hi = p["ts"], p["ts"] + p["dur"]
        while j < len(kids) and kids[j][0] < lo:
            j += 1
        inside, k = 0.0, j
        while k < len(kids) and kids[k][0] < hi:
            if kids[k][1] <= hi:
                inside += kids[k][1] - kids[k][0]
            k += 1
        out.append((p["dur"] - inside) / 1e3)
    return out


def mean(xs: List[float]):
    return sum(xs) / len(xs) if xs else None
