"""Peak rates per device kind, and the work each planner kernel needs.

The work is what the algorithm needs, computed from the call's shapes,
never what one implementation happens to do (padding, a materialized
pairwise matrix, extra passes): a later kernel that computes the same
result is read against the same work. A kernel's least time is the
larger of operations over the peak rate and bytes over the peak
bandwidth; which of the two is larger is the bound that applies.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and 819 GB/s
# of HBM bandwidth per chip. JAX reports a v5e chip as "TPU v5 lite".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e" (per chip)'

F32 = 4  # bytes
BOOL = 1


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def pareto_mask_work(b: int, g: int) -> Tuple[float, float]:
    """Keep-set of B rows over a G-point grid. Reads each row's f32 step
    times, the shared f32 power row and the feasibility mask, writes the
    keep mask. A sort by time and one running minimum over energy per
    row is G log2 G comparisons plus the G products E = W T."""
    ops = b * (g * math.ceil(math.log2(max(g, 2))) + g)
    nbytes = b * g * (F32 + BOOL + BOOL) + g * F32
    return float(ops), float(nbytes)


def plan_argmin_work(b: int, g: int) -> Tuple[float, float]:
    """Masked argmin of (W T) T^k over B rows of G points: reads the f32
    step times, the mask, the power row and one exponent per row, writes
    one int32 index per row; four operations per point (the metric's
    product and power, the mask select, the running minimum)."""
    ops = 4 * b * g
    nbytes = b * g * (F32 + BOOL) + g * F32 + b * F32 + b * F32
    return float(ops), float(nbytes)


def rbf_gram_work(batch: int, n: int, m: int, d: int) -> Tuple[float, float]:
    """K = exp(-gamma |x_i - y_j|^2) for ``batch`` (n, d) x (m, d) pairs:
    per entry, a difference, a square and an add per feature, then the
    scale and the exponential; reads both f32 point sets, writes K."""
    ops = batch * n * m * (3 * d + 2)
    nbytes = batch * ((n + m) * d + n * m) * F32
    return float(ops), float(nbytes)


WORK = {
    "pareto_mask": pareto_mask_work,
    "plan_argmin": plan_argmin_work,
    "rbf_gram": rbf_gram_work,
}


def least_time(ops: float, nbytes: float, device_kind: str) -> Tuple[float, str]:
    """(seconds, bound): the time the chip needs at least for the work."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "operations")


def roofline_share(calls, kernel: str, device_s: float, device_kind: str):
    """Per cent of the roofline that ``device_s`` of kernel time reached
    over the recorded call shapes; None when there is nothing to read."""
    if not calls or device_s <= 0.0:
        return None
    least = sum(least_time(*WORK[kernel](*shape), device_kind)[0] for shape in calls)
    return 100.0 * least / device_s
