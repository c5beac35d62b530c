"""The plain reference: NumPy float64, importing nothing of the program.

Four layers are compared against it after the measured window, each
from the data the run measured (the node's stress samples, each
family's characterization samples), never from what the program made
of them:

* power: Eq. 7 fitted by float64 least squares from the stress samples
  and evaluated on the planning grid;
* characterization: an epsilon-SVR step-time surface fitted in float64
  from the same training samples (RBF Gram, active-set dual solve with a
  ridge ladder) and evaluated on the planning grid;
* engine: the energy of each plan of the window on those float64
  surfaces, against the cheapest feasible grid point (Eq. 8);
* service: every job in exactly one place, an honest energy ledger and
  no oversubscribed node.

``gram=`` and ``round_to=`` exist for the control only: the same
reference computed one precision below what the configuration states.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

TIME_FLOOR = 1e-6  # seconds; the planning grid's floor on a step time


# ---------------------------------------------------------------------------
# characterization: epsilon-SVR, RBF kernel, float64
# ---------------------------------------------------------------------------


def gram64(x: np.ndarray, y: np.ndarray, gamma: float) -> np.ndarray:
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    d2 = np.sum((x[..., :, None, :] - y[..., None, :, :]) ** 2, axis=-1)
    return np.exp(-gamma * d2)


def _active_set(K, y, C, eps, lam, max_rounds=30):
    """One epsilon-SVR dual, beta = alpha - alpha*, by an active-set method:
    free duals solve (K + lam I) beta + b = y - eps sign(beta) with
    sum(beta) = 0; duals past the box |beta| <= C are pinned at the bound,
    the worst quarter of violators per round, and released again when
    their KKT multiplier changes sign. Keeps the clean iterate with the
    lowest dual objective. Returns (beta, bias)."""
    n = len(y)
    bound = np.zeros(n, bool)
    beta = np.zeros(n)
    sign = np.zeros(n)
    sign_prev = np.full(n, 2.0)
    best = (np.zeros(n), float(np.median(y)), 0.0)
    for _ in range(max_rounds):
        free = ~bound
        A = np.zeros((n + 1, n + 1))
        A[:n, :n] = K
        A[np.arange(n), np.arange(n)] += lam
        A[:n, n] = 1.0
        pinned = np.flatnonzero(bound)
        A[pinned, :] = 0.0
        A[pinned, pinned] = 1.0
        A[n, :n] = 1.0
        if not free.any():
            A[n, :] = 0.0
            A[n, n] = 1.0
        rhs = np.zeros(n + 1)
        rhs[:n] = y - eps * sign
        rhs[pinned] = beta[pinned]
        sol = np.linalg.solve(A, rhs)
        b_sol, bias = sol[:n], sol[n]
        new = np.where(free, np.clip(b_sol, -C, C), beta)
        sign_new = np.where(free, np.sign(b_sol), sign)
        viol = free & (np.abs(b_sol) > C)
        obj = 0.5 * new @ K @ new - y @ new + eps * np.abs(new).sum()
        if not viol.any() and obj < best[2]:
            best = (new.copy(), float(bias), float(obj))
        grad = K @ new + lam * new - y + bias
        moved = False
        if viol.any():
            over = np.where(viol, np.abs(b_sol) - C, -np.inf)
            k = max(1, int(viol.sum() // 4))
            bound[np.argsort(-over)[:k]] = True
            moved = True
        elif bound.any():
            release = bound & (
                ((new >= C - 1e-12) & (grad + eps > 1e-6))
                | ((new <= -C + 1e-12) & (grad - eps < -1e-6))
            )
            if release.any():
                bound[release] = False
                moved = True
        stable = bool((sign_new == sign).all())
        cycled = bool((sign_new == sign_prev).all())
        beta, sign_prev, sign = new, sign, sign_new
        if not moved and (stable or cycled):
            break
    return best[0], best[1]


def fit64(x, y, *, C=10e3, gamma=0.5, eps=1e-4, ridge=1e-3, log_target=True,
          standardize=True, gram=gram64):
    """Fit one surface; returns a dict the predictor reads. ``gram`` is the
    Gram function (the control swaps in a lower-precision one)."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    if log_target:
        y = np.log(np.maximum(y, 1e-12))
    if standardize:
        x_mean, x_std = x.mean(0), x.std(0) + 1e-8
        y_mean, y_std = y.mean(), y.std() + 1e-8
    else:
        x_mean, x_std = np.zeros(x.shape[1]), np.ones(x.shape[1])
        y_mean, y_std = 0.0, 1.0
    xs = (x - x_mean) / x_std
    ys = (y - y_mean) / y_std
    K = np.asarray(gram(xs, xs, gamma), np.float64)
    C_s, eps_s = C / y_std, eps / y_std
    best_rel, out = np.inf, None
    for lam in (ridge, 3 * ridge, 10 * ridge, 100 * ridge):
        beta, bias = _active_set(K, ys, C_s, eps_s, lam)
        resid = np.abs(K @ beta + bias - ys)
        rel = float(np.mean(resid / np.maximum(np.abs(ys), 1e-9)))
        if rel < best_rel:
            best_rel, out = rel, (beta, bias)
        if rel < 0.10:
            break
    return dict(x_train=xs, beta=out[0], bias=out[1], gamma=gamma,
                x_mean=x_mean, x_std=x_std, y_mean=y_mean, y_std=y_std,
                log_target=log_target)


def predict64(model: dict, x: np.ndarray, gram=gram64) -> np.ndarray:
    xs = (np.asarray(x, np.float64) - model["x_mean"]) / model["x_std"]
    k = np.asarray(gram(xs, model["x_train"], model["gamma"]), np.float64)
    out = (k @ model["beta"] + model["bias"]) * model["y_std"] + model["y_mean"]
    return np.exp(out) if model["log_target"] else out


def surface_max_rel(got: np.ndarray, want: np.ndarray) -> float:
    """Widest relative gap between a planned surface and the reference."""
    got = np.asarray(got, np.float64)
    want = np.maximum(np.asarray(want, np.float64), TIME_FLOOR)
    return float(np.max(np.abs(got - want) / np.abs(want)))


# ---------------------------------------------------------------------------
# power: Eq. 7 fitted by least squares from the stress samples
# ---------------------------------------------------------------------------


def fit_power64(f, p, s, watts, *, round_to=None) -> Tuple[float, float, float, float]:
    """(c1, c2, c3, c4) of W = p (c1 f^3 + c2 f) + c3 + c4 s by minimum-norm
    least squares in float64. ``round_to`` (the control only) rounds the
    design matrix and the watts to that type first."""
    f, p, s, w = (np.asarray(a, np.float64) for a in (f, p, s, watts))
    X = np.stack([p * f**3, p * f, np.ones_like(f), s], axis=-1)
    if round_to is not None:
        X = X.astype(round_to).astype(np.float64)
        w = w.astype(round_to).astype(np.float64)
    return tuple(float(c) for c in np.linalg.lstsq(X, w, rcond=None)[0])


def power_grid64(coeffs, F, C, P) -> np.ndarray:
    """Eq. 7 of the paper, W = p (c1 f^3 + c2 f) + c3 + c4 s, in float64."""
    c1, c2, c3, c4 = (float(c) for c in coeffs)
    F, C, P = (np.asarray(a, np.float64) for a in (F, C, P))
    return C * (c1 * F**3 + c2 * F) + c3 + c4 * P


# ---------------------------------------------------------------------------
# engine: the energy argmin over the (frequency x cores) grid (Eq. 8)
# ---------------------------------------------------------------------------


def constraint_masks(T, F, C, constraints) -> np.ndarray:
    """Each row's feasible grid points; an empty row falls back to the
    near-fastest points that keep every constraint but the time bound
    (the engine's ``on_infeasible="fastest"``). ``constraints`` holds one
    (max_time_s, max_cores, min_f, max_f) tuple per row, None for unset."""
    F, C = np.ravel(F), np.ravel(C)
    masks = np.ones(T.shape, bool)
    for i, (max_t, max_c, min_f, max_f) in enumerate(constraints):
        relaxed = np.ones(T.shape[1], bool)
        if max_c is not None:
            relaxed &= C <= max_c
        if min_f is not None:
            relaxed &= F >= min_f
        if max_f is not None:
            relaxed &= F <= max_f
        m = relaxed if max_t is None else relaxed & (T[i] <= max_t)
        if not m.any():
            if not relaxed.any():
                relaxed[:] = True
            t_min = T[i][relaxed].min()
            m = relaxed & (T[i] <= t_min * (1.0 + 1e-3))
        masks[i] = m
    return masks


def plan_regret(chosen: np.ndarray, E: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Per row, how much more energy the chosen grid point needs than the
    cheapest feasible one, as a share of the latter: 0 for the argmin,
    and for a point outside the mask its energy counts all the same."""
    best = np.min(np.where(mask, E, np.inf), axis=1)
    got = E[np.arange(len(chosen)), chosen]
    return got / best - 1.0


# ---------------------------------------------------------------------------
# service: placements, ledger, capacity
# ---------------------------------------------------------------------------


def free_at(segments, t: float, max_cores: int) -> int:
    """Free cores at instant t over half-open [start, end) segments."""
    used = sum(c for s, e, c in segments if s <= t < e)
    return max_cores - used


def oversubscribed(segments, max_cores: int, eps: float = 1e-9) -> bool:
    """True when some instant holds more cores than the node has. The
    busiest instants are segment starts; each start is probed a hair
    inside its interval so touching [a, b) [b, c) pairs never overlap."""
    for s, e, _ in segments:
        t = s + eps * max(1.0, abs(s))
        if t < e and free_at(segments, t, max_cores) < 0:
            return True
    return False


def schedule_violations(
    submitted: Sequence[int],
    completed: Sequence[Tuple[int, float, float, float]],
    in_flight: Sequence[int],
    pending: Sequence[int],
    fleet_total_j: float,
    nodes: Sequence[Tuple[str, int, list]],
) -> Dict[str, int]:
    """Counts of broken guarantees; every count is 0 on a sound run.

    completed: (job_id, total_energy_j, segment_energy_j, prior_energy_j)
    per finished job; nodes: (name, max_cores, [(start, end, cores)]).
    """
    done = [c[0] for c in completed]
    placed = done + list(in_flight)
    where = placed + list(pending)
    out = {
        "jobs_placed_twice": len(placed) - len(set(placed)),
        "jobs_lost": len(set(submitted) - set(where)),
        "jobs_unknown": len(set(where) - set(submitted)),
        "ledger_dishonest": sum(
            1 for _, tot, seg, prior in completed if tot != seg + prior
        ),
        "nodes_oversubscribed": sum(
            1 for _, cap, segs in nodes if oversubscribed(segs, cap)
        ),
    }
    total = sum(c[1] for c in completed)
    out["fleet_total_off"] = int(not math.isclose(fleet_total_j, total))
    return out
