"""From a profiler trace to device numbers: busy time, idle gaps, kernel
time, and what the host was doing in each gap.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``; every other function works on plain
``(start_ns, end_ns, name)`` tuples, so the arithmetic is tested on
synthetic events.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int, str]

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """Device op events per device plane, and every host event by name.

    Returns ({plane name: [(start_ns, end_ns, op name)]},
             {event name: [(start_ns, end_ns)]})."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: Dict[str, List[Tuple[int, int]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX) and plane.name[len(DEVICE_PLANE_PREFIX):].isdigit():
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((s, s + int(ev.duration_ns), op_label(ev.name, ev.stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.setdefault(ev.name, []).append((s, s + int(ev.duration_ns)))
    return devices, host


def op_label(name: str, stats) -> str:
    """One device op's label: its HLO name and the name stack JAX gave it
    (``tf_op``: e.g. ``jit(fn)/pareto_mask_pallas/pallas_call``), which is
    where a kernel's jitted wrapper names it."""
    stats = dict(stats)
    parts = [str(stats[k]) for k in ("tf_op", "hlo_op") if stats.get(k)]
    return " | ".join(parts + [name.split(" = ")[0]])


def clip(events: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events if e > lo and s < hi]


def merge(events: Iterable[Interval]) -> List[Tuple[int, int]]:
    """The union of the intervals, as sorted disjoint (start, end) pairs."""
    out: List[List[int]] = []
    for s, e, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merge(clip(events, lo, hi)))


def gaps(events: Iterable[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of [lo, hi): where no device op runs."""
    out, t = [], lo
    for s, e in merge(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def kernel_ns(events: Iterable[Interval], pattern: str, lo: int, hi: int) -> int:
    """Summed device time of the ops whose name contains ``pattern``."""
    return sum(e - s for s, e, n in clip(events, lo, hi) if pattern in n)


def top_ops(events: Iterable[Interval], lo: int, hi: int, k: int = 10):
    """[(op name, seconds)] of the k ops that took most device time."""
    tot: Dict[str, int] = {}
    for s, e, n in clip(events, lo, hi):
        tot[n] = tot.get(n, 0) + (e - s)
    return [[n, ns / 1e9] for n, ns in sorted(tot.items(), key=lambda x: -x[1])[:k]]


class SpanIndex:
    """The host spans of one thread (they nest), for looking up the
    innermost span open at an instant."""

    def __init__(self, spans: Sequence[Interval]):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.reach = []  # running max of ends: no span before i reaches past it
        top = None
        for _, e, _ in self.spans:
            top = e if top is None else max(top, e)
            self.reach.append(top)

    def innermost(self, t: float) -> Optional[str]:
        """The latest-starting span that contains t, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] >= t:
            s, e, n = self.spans[i]
            if e >= t:
                return n
            i -= 1
        return None


def attribute_gaps(gap_list, spans: Sequence[Interval], outside: str = "(no span)"):
    """Idle device seconds summed by the host span open at each gap's
    midpoint, largest first."""
    index = SpanIndex(spans)
    tot: Dict[str, int] = {}
    for s, e in gap_list:
        name = index.innermost((s + e) / 2) or outside
        tot[name] = tot.get(name, 0) + (e - s)
    return [[n, ns / 1e9] for n, ns in sorted(tot.items(), key=lambda x: -x[1])]
