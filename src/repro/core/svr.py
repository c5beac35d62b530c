"""ε-Support-Vector-Regression with RBF kernel, in JAX (paper §2.2).

The paper characterizes application performance as T = SVR(f, p, N) with an
RBF kernel, C = 10·10^3, γ = 0.5, trained on execution-time samples over the
(frequency, cores, input-size) grid and validated with 10-fold CV.

We solve the standard ε-SVR dual in the β = α - α* parametrization:

    max_β  -½ βᵀ K β + yᵀ β - ε ‖β‖₁     s.t.  Σβ = 0,  |β_i| ≤ C

with a float64 active-set method (equality-constrained KKT solves with
box-bounded duals pinned by identity rows, KKT-driven bind/release),
optionally polished by a monotone projected proximal-gradient (ISTA) pass.
The Gram matrix — the compute hotspot — goes through ``kernels.ops.rbf_gram``
(Pallas on TPU). Bias b comes from the KKT system directly.

**Batched fits** (``fit_many``) are the hot path since PR 2: many same-shape
training sets (one per workload family / application) are stacked — ragged
sets padded with masked rows — their Gram tensor is built in ONE
``rbf_gram`` call, the active-set KKT solves run batched over the leading
dim (``np.linalg.solve`` on the (B, n+1, n+1) stack), and the optional ISTA
polish is one ``vmap``ped pass. ``fit`` is a thin B = 1 wrapper, so single
and batched fits share one numerical path.

Features/targets are RAW by default (paper-faithful; the paper's γ = 0.5 is
calibrated to raw (f, p, N) axes); ``standardize=True`` is available for
planner-scale feature ranges.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import rff as rff_mod
from repro.kernels import ops

# f32 matmuls at full precision: XLA's and Mosaic's default on a TPU is one
# bf16 pass, which moved held-out step-time predictions by up to ~100% on a
# v5e against a float64 fit. Every Gram contraction and matvec uses this.
_HIGHEST = jax.lax.Precision.HIGHEST
_matmul = functools.partial(jnp.matmul, precision=_HIGHEST)

# ``method="auto"`` switch point for fit_many: sets with at least this many
# samples take the random-Fourier-feature path (linear in n) instead of the
# exact O(n^3) dual solve. The engine's per-family sweeps (a few dozen
# samples) stay exact, so default planner behavior is unchanged; drift
# refits over large telemetry windows cross it and go linear.
RFF_THRESHOLD = 1024


@dataclasses.dataclass
class SVRParams:
    """Fitted model state (a pytree-of-arrays + static hyper-params)."""

    x_train: jnp.ndarray  # (n, d) standardized
    beta: jnp.ndarray  # (n,) dual coefficients
    bias: float
    gamma: float
    x_mean: jnp.ndarray
    x_std: jnp.ndarray
    y_mean: float
    y_std: float
    log_target: bool = False


def _project_sum_zero_box(
    beta: jnp.ndarray, C, mask: Optional[jnp.ndarray] = None, iters: int = 50
) -> jnp.ndarray:
    """Project onto {Σβ = 0, |β_i| ≤ C}: bisection on λ in clip(β-λ,-C,C).

    ``mask`` marks the real rows of a padded problem: masked-out entries are
    pinned to 0 and excluded from the Σβ = 0 constraint.
    """
    m = jnp.ones_like(beta) if mask is None else mask.astype(beta.dtype)

    def s(lam):
        return jnp.sum(m * jnp.clip(beta - lam, -C, C))

    lo = jnp.min(jnp.where(m > 0, beta, jnp.inf)) - C
    hi = jnp.max(jnp.where(m > 0, beta, -jnp.inf)) + C

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        smid = s(mid)
        lo = jnp.where(smid > 0, mid, lo)
        hi = jnp.where(smid > 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    lam = 0.5 * (lo + hi)
    return m * jnp.clip(beta - lam, -C, C)


def _active_set_solve_batch(
    K: np.ndarray,
    y: np.ndarray,
    C: np.ndarray,
    eps: np.ndarray,
    mask: np.ndarray,
    *,
    lam: float = 1e-3,
    max_rounds: int = 30,
):
    """Batched active-set solve of B ε-SVR duals (float64, exact up to the
    tiny ridge λ used for conditioning of the near-singular RBF Gram).

    K: (B, n, n) Gram stack (padded rows/cols zeroed), y: (B, n), C/eps:
    (B,) per-item box/tube in standardized units, mask: (B, n) real rows.

    KKT structure per item: free SVs satisfy
    (Kβ)_i + λβ_i + b = y_i − ε·sign(β_i); box-bounded SVs sit at ±C. Every
    item solves one (n+1)×(n+1) system per round — bound and padded duals
    are pinned by identity rows instead of being folded into a shrunken free
    system, which keeps the whole batch a single ``np.linalg.solve`` on the
    (B, n+1, n+1) stack. Per round:
      1. batched solve of the pinned KKT systems,
      2. clip any |β_free| > C to the bound; bind only the worst quartile
         of violators (binding everything at once overshoots — each clipped
         dual perturbs all others through the kernel),
      3. after a CLEAN solve, release bounded points whose KKT multiplier
         sign flipped (a just-clipped iterate has a stale gradient and
         would release its own binding immediately). A point at +C is
         optimal iff (Kβ)_i + λβ_i − y_i + ε + b ≤ 0 (symmetric at −C).
    Items converge independently (3–5 rounds in practice) and are dropped
    from later rounds; a near-zero dual whose ε-tube sign dithers produces a
    period-2 solution cycle, detected and stopped after both states have
    been scored (the best-candidate tracker has already seen the whole
    cycle, so this changes nothing but the round count). NOTE: a plain
    "solve then clip" is *globally* destructive for wide RBF kernels — the
    re-solve with pinned bounds is what makes this work. Returns
    (beta (B, n), bias (B,)).
    """
    B, n = y.shape
    K64 = np.asarray(K, np.float64)
    y64 = np.asarray(y, np.float64)
    bound = np.zeros((B, n), bool)
    beta = np.zeros((B, n))
    sign = np.zeros((B, n))
    sign_prev = np.full((B, n), 2.0)  # sentinel: matches no real sign pattern

    best_beta = np.zeros((B, n))
    best_bias = np.array(
        [float(np.median(y64[i, mask[i]])) if mask[i].any() else 0.0 for i in range(B)]
    )
    best_obj = np.zeros(B)  # dual objective of β = 0
    done = np.zeros(B, bool)

    for _ in range(max_rounds):
        act = np.where(~done)[0]
        if act.size == 0:
            break
        Ka, ya = K64[act], y64[act]
        Ca, ea = C[act][:, None], eps[act][:, None]
        free = mask[act] & ~bound[act]
        nf = free.sum(1)

        A = np.zeros((act.size, n + 1, n + 1))
        rhs = np.zeros((act.size, n + 1))
        A[:, :n, :n] = Ka
        A[:, np.arange(n), np.arange(n)] += lam
        A[:, :n, n] = 1.0
        pi, pj = np.nonzero(~free)  # pin bound/padded duals: identity rows
        A[pi, pj, :] = 0.0
        A[pi, pj, pj] = 1.0
        A[:, n, :n] = mask[act].astype(float)  # Σβ = 0 over real rows
        degenerate = nf == 0  # all real duals bound: b has no equation left;
        A[degenerate, n, :] = 0.0  # replace the Σβ row outright with b = 0
        A[degenerate, n, n] = 1.0
        rhs[:, :n] = ya - ea * sign[act]
        rhs[pi, pj] = np.where(bound[act][pi, pj], beta[act][pi, pj], 0.0)
        sol = np.linalg.solve(A, rhs[..., None])[..., 0]
        beta_sol, b_sol = sol[:, :n], sol[:, n]

        beta_new = np.where(free, np.clip(beta_sol, -Ca, Ca), beta[act])
        sign_new = np.where(free, np.sign(beta_sol), sign[act])
        viol = free & (np.abs(beta_sol) > Ca)
        clean = ~viol.any(1)

        obj = (
            0.5 * np.einsum("bi,bij,bj->b", beta_new, Ka, beta_new)
            - np.einsum("bi,bi->b", ya, beta_new)
            + eps[act] * np.abs(beta_new).sum(1)
        )
        take = clean & (obj < best_obj[act])
        best_beta[act[take]] = beta_new[take]
        best_bias[act[take]] = b_sol[take]
        best_obj[act[take]] = obj[take]

        grad = (
            np.einsum("bij,bj->bi", Ka, beta_new)
            + lam * beta_new
            - ya
            + b_sol[:, None]
        )
        moved = np.zeros(act.size, bool)
        for j in range(act.size):
            i = act[j]
            if viol[j].any():
                over = np.where(viol[j], np.abs(beta_sol[j]) - C[i], -np.inf)
                k = max(1, int(viol[j].sum() // 4))
                bound[i, np.argsort(-over)[:k]] = True
                moved[j] = True
            elif bound[i].any():
                release = bound[i] & (
                    ((beta_new[j] >= C[i] - 1e-12) & (grad[j] + eps[i] > 1e-6))
                    | ((beta_new[j] <= -C[i] + 1e-12) & (grad[j] - eps[i] < -1e-6))
                )
                if release.any():
                    bound[i, release] = False
                    moved[j] = True

        stable = (sign_new == sign[act]).all(1)
        cycled = (sign_new == sign_prev[act]).all(1)
        beta[act] = beta_new
        sign_prev[act] = sign[act]
        sign[act] = sign_new
        done[act] |= (~moved) & (stable | cycled)

    return best_beta, best_bias


def _solve_dual_ladder(
    K: np.ndarray,
    y: np.ndarray,
    C: np.ndarray,
    eps: np.ndarray,
    mask: np.ndarray,
    ridge: float,
):
    """Per-item ridge escalation over the batched active-set solve.

    On unlucky noise draws the box constraint binds marginally and the
    active-set solve can stall at the flat fallback (a constant predictor —
    which downstream energy minimization would happily "optimize" to the
    minimum-power corner). Escalate the conditioning ridge until the
    training fit is sane; items that reach relative residual < 0.10 drop
    out of the remaining rungs, so well-conditioned batches pay one rung.
    """
    B, n = y.shape
    best_rel = np.full(B, np.inf)
    out_beta = np.zeros((B, n))
    out_bias = np.zeros(B)
    todo = np.arange(B)
    for lam in (ridge, 3 * ridge, 10 * ridge, 100 * ridge):
        if todo.size == 0:
            break
        beta, bias = _active_set_solve_batch(
            K[todo], y[todo], C[todo], eps[todo], mask[todo], lam=lam
        )
        resid = np.abs(
            np.einsum("bij,bj->bi", K[todo], beta) + bias[:, None] - y[todo]
        )
        rel = (
            np.where(mask[todo], resid / np.maximum(np.abs(y[todo]), 1e-9), 0.0).sum(1)
            / np.maximum(mask[todo].sum(1), 1)
        )
        better = rel < best_rel[todo]
        upd = todo[better]
        out_beta[upd] = beta[better]
        out_bias[upd] = bias[better]
        best_rel[upd] = rel[better]
        todo = todo[rel >= 0.10]
    return out_beta, out_bias


def _ista_refine_masked(
    K: jnp.ndarray,
    y: jnp.ndarray,
    beta0: jnp.ndarray,
    C,
    eps,
    mask: jnp.ndarray,
    iters: int = 200,
):
    """Monotone proximal-gradient refinement of the warm start towards the
    true ε-SVR optimum: step 1/λ_max(K), soft-threshold for ε‖β‖₁, exact
    projection onto {Σβ=0, |β|≤C, β_pad=0}. Keeps the best-objective iterate
    (ISTA on this near-singular K is descent-stable where FISTA momentum is
    not). One padded item of the batch — ``fit_many`` vmaps this."""
    n = K.shape[0]
    m = mask.astype(K.dtype)

    def power_step(_, v):
        w = _matmul(K, v)
        return w / (jnp.linalg.norm(w) + 1e-12)

    v0 = m / jnp.sqrt(jnp.maximum(jnp.sum(m), 1.0))
    v = jax.lax.fori_loop(0, 50, power_step, v0)
    L = jnp.maximum(_matmul(v, _matmul(K, v)), 1e-6)
    step = 0.9 / L

    def obj(b):
        quad = _matmul(b, _matmul(K, b))
        return 0.5 * quad - _matmul(y, b) + eps * jnp.sum(jnp.abs(b))

    def body(_, carry):
        beta, best, best_obj = carry
        z = beta - step * (_matmul(K, beta) - y)
        z = jnp.sign(z) * jnp.maximum(jnp.abs(z) - step * eps, 0.0)
        beta_new = _project_sum_zero_box(z, C, mask)
        o = obj(beta_new)
        take = o < best_obj
        best = jnp.where(take, beta_new, best)
        best_obj = jnp.where(take, o, best_obj)
        return beta_new, best, best_obj

    beta0 = jnp.asarray(beta0, K.dtype) * m
    _, best, _ = jax.lax.fori_loop(0, iters, body, (beta0, beta0, obj(beta0)))
    return best


@functools.partial(jax.jit, static_argnames=("iters",))
def _ista_refine_batch(K, y, beta0, C, eps, mask, iters: int = 200):
    """The batched ISTA polish: ONE vmapped pass over the (B, n, n) Gram
    stack. Compiles once per (B, n) shape."""
    return jax.vmap(
        lambda K_, y_, b_, C_, e_, m_: _ista_refine_masked(
            K_, y_, b_, C_, e_, m_, iters
        )
    )(K, y, beta0, C, eps, mask)


@functools.partial(jax.jit, static_argnames=("iters",))
def _ista_refine(
    K: jnp.ndarray,
    y: jnp.ndarray,
    beta0: jnp.ndarray,
    C: float,
    eps: float,
    iters: int = 200,
):
    """Single-problem ISTA refine (B = 1 view of ``_ista_refine_masked``)."""
    return _ista_refine_masked(
        K, y, beta0, C, eps, jnp.ones(K.shape[0], bool), iters
    )


def _recover_bias_masked(
    K: jnp.ndarray, y: jnp.ndarray, beta: jnp.ndarray, C, eps, mask: jnp.ndarray
) -> jnp.ndarray:
    """KKT: for free SVs (0 < |β| < C):  b = y_i - (Kβ)_i - sign(β_i)·ε."""
    f = _matmul(K, beta)
    tol = 1e-6 * C
    free = mask & (jnp.abs(beta) > tol) & (jnp.abs(beta) < C - tol)
    cand = y - f - jnp.sign(beta) * eps
    n_free = jnp.sum(free)
    b_free = jnp.sum(jnp.where(free, cand, 0.0)) / jnp.maximum(n_free, 1)
    b_fallback = jnp.nanmedian(jnp.where(mask, y - f, jnp.nan))
    return jnp.where(n_free > 0, b_free, b_fallback)


def _recover_bias(
    K: jnp.ndarray, y: jnp.ndarray, beta: jnp.ndarray, C: float, eps: float
) -> jnp.ndarray:
    return _recover_bias_masked(K, y, beta, C, eps, jnp.ones(K.shape[0], bool))


@functools.partial(jax.jit, static_argnames=("gamma", "impl"))
def _gram_batched(x, y, gamma, impl):
    """Jitted batched Gram build: compiles once per (B, n) shape — the
    eager vmapped dispatch otherwise dominates small-batch fit time."""
    return ops.rbf_gram(x, y, gamma, impl=impl)


def _as_xy(item):
    """Accept a (x, y) pair or a Characterization-like (.features/.times)."""
    feats = getattr(item, "features", None)
    if feats is not None:
        return np.asarray(feats), np.asarray(item.times)
    x, y = item
    return np.asarray(x), np.asarray(y)


def _fit_meta(x_mean, x_std, y_mean, y_std, eps: float, C: float):
    """One item's standardization record. ε and C are specified in
    raw-target units; the rescale to standardized units lives ONLY here —
    both preprocessing branches (vectorized same-shape, per-item ragged)
    must agree on it or fit/fit_many parity breaks."""
    return (
        x_mean,
        x_std,
        float(y_mean),
        float(y_std),
        eps / float(y_std),
        C / float(y_std),
    )


def fit_many(
    sets: Sequence,
    *,
    C: float = 10e3,
    gamma: float = 0.5,
    eps: float = 0.01,
    iters: int = 0,
    impl: Optional[str] = None,
    log_target: bool = False,
    standardize: bool = False,
    ridge: float = 1e-3,
    method: str = "exact",
    rff_features: Optional[int] = None,
    rff_seed: Optional[int] = None,
    rff_ridge: Optional[float] = None,
    rff_threshold: Optional[int] = None,
) -> list:
    """Fit B ε-SVR models in one batched pass — one model per training set.

    ``sets`` is a sequence of (x, y) pairs or Characterization-like objects
    (``.features``/``.times``); hyper-parameters are shared across the batch
    (one workload *family* per set is the intended use). Ragged sets are
    padded to the longest with masked rows, then:

      * ONE batched ``rbf_gram`` call builds the (B, n, n) Gram tensor,
      * the active-set KKT systems solve as one ``np.linalg.solve`` on the
        (B, n+1, n+1) stack per round (per-item ridge escalation, items
        dropping out as they converge),
      * the optional ISTA polish (``iters > 0``) is one vmapped jitted pass.

    Args:
        sets: B training sets. Per set: x (n, d) raw features — for the
            paper's surfaces (frequency GHz, cores, input size) — and
            y (n,) raw targets in seconds.
        C / eps: the ε-SVR box bound and tube, in raw-target units
            (seconds; rescaled internally when ``standardize``).
        gamma: RBF width on the (possibly standardized) feature axes.
        iters: ISTA polish iterations (0 = active-set solution only).
        log_target / standardize: the beyond-paper mode for features
            spanning orders of magnitude (the TPU planner / engine path).
        ridge: base conditioning ridge for the KKT solves.
        method: ``"exact"`` (default) solves the ε-SVR dual; ``"rff"``
            fits a random-Fourier-feature ridge approximation
            (``core.rff``, linear in sample count); ``"auto"`` routes
            each set by size — exact below ``rff_threshold`` samples
            (default ``RFF_THRESHOLD``), RFF at or above it. Mixed
            batches split, fit each way, and merge back in order.
        rff_features / rff_seed / rff_ridge: RFF path knobs (feature
            count D, deterministic spectral seed, relative ridge);
            ``None`` takes the ``core.rff`` module defaults.

    RFF-path models come back as ``rff.RFFParams`` (not ``SVRParams``);
    ``predict`` / ``predict_many`` / ``predict_each`` dispatch on the
    type, so downstream callers are agnostic.

    Returns:
        ``List[SVRParams]`` aligned with ``sets``; ``predict(model, x)``
        yields seconds. ``fit`` is the B = 1 wrapper, so batched and
        sequential fits share one numerical path (parity up to
        batched-LAPACK reduction order).

    Example — two families, one batched solve::

        import numpy as np
        from repro.core import svr
        x = np.array([[1.2, 4.0], [1.8, 8.0], [2.2, 16.0]], np.float32)
        sets = [(x, np.array([4.0, 2.0, 1.0], np.float32)),
                (x, np.array([8.0, 5.0, 3.0], np.float32))]
        m_a, m_b = svr.fit_many(sets, gamma=0.5)
        t_pred = svr.predict(m_a, x)  # seconds, aligned with x
    """
    pairs = [_as_xy(s) for s in sets]
    if not pairs:
        return []

    if method not in ("exact", "rff", "auto"):
        raise ValueError(f"unknown fit method: {method!r}")
    if method != "exact":
        thr = RFF_THRESHOLD if rff_threshold is None else int(rff_threshold)
        use_rff = [
            method == "rff" or int(np.shape(x)[0]) >= thr for x, _ in pairs
        ]
        if any(use_rff):
            rff_kw = dict(
                gamma=gamma,
                log_target=log_target,
                standardize=standardize,
                n_features=rff_features,
                seed=rff_seed,
                ridge=rff_ridge,
            )
            # flight-recorder route accounting: the RFF side is counted
            # here at the dispatch; the exact side is counted once, at the
            # plain solve below (the mixed branch RECURSES into fit_many
            # for its exact half, so counting it here would double-count)
            if all(use_rff):
                obs.counter("svr.fit_route_rff").inc(len(pairs))
                return rff_mod.fit_many_rff(pairs, **rff_kw)
            # mixed batch: split by route, fit each side its own way,
            # merge back into input order
            rff_idx = [i for i, u in enumerate(use_rff) if u]
            obs.counter("svr.fit_route_rff").inc(len(rff_idx))
            exact_idx = [i for i, u in enumerate(use_rff) if not u]
            merged: list = [None] * len(pairs)
            for i, m in zip(
                rff_idx, rff_mod.fit_many_rff([pairs[i] for i in rff_idx], **rff_kw)
            ):
                merged[i] = m
            exact_models = fit_many(
                [pairs[i] for i in exact_idx],
                C=C,
                gamma=gamma,
                eps=eps,
                iters=iters,
                impl=impl,
                log_target=log_target,
                standardize=standardize,
                ridge=ridge,
            )
            for i, m in zip(exact_idx, exact_models):
                merged[i] = m
            return merged

    obs.counter("svr.fit_route_exact").inc(len(pairs))

    # preprocessing stays in numpy: per-item jnp dispatches here would eat
    # the batching win before the solver even runs. Same-shape batches (the
    # engine's per-family sets) standardize as one vectorized pass.
    B = len(pairs)
    ns = [int(np.shape(p[0])[0]) for p in pairs]
    n_max = max(ns)
    d = int(np.shape(pairs[0][0])[1])
    if len(set(ns)) == 1:
        X = np.stack([np.asarray(x, np.float32) for x, _ in pairs])
        Y = np.stack([np.asarray(y, np.float32) for _, y in pairs])
        if log_target:
            Y = np.log(np.maximum(Y, 1e-12))
        if standardize:
            x_mean = np.mean(X, axis=1)
            x_std = np.std(X, axis=1) + np.float32(1e-8)
            y_mean = np.mean(Y, axis=1).astype(np.float32)
            y_std = (np.std(Y, axis=1) + 1e-8).astype(np.float32)
        else:
            x_mean = np.zeros((B, d), np.float32)
            x_std = np.ones((B, d), np.float32)
            y_mean = np.zeros(B, np.float32)
            y_std = np.ones(B, np.float32)
        Xp = ((X - x_mean[:, None, :]) / x_std[:, None, :]).astype(np.float32)
        Yp = ((Y - y_mean[:, None]) / y_std[:, None]).astype(np.float32)
        mask = np.ones((B, n_max), bool)
        xs_std = list(Xp)
        metas = [
            _fit_meta(x_mean[i], x_std[i], y_mean[i], y_std[i], eps, C)
            for i in range(B)
        ]
    else:
        xs_std, ys_std, metas = [], [], []
        for x_raw, y_raw in pairs:
            x = np.asarray(x_raw, np.float32)
            y = np.asarray(y_raw, np.float32)
            if log_target:
                y = np.log(np.maximum(y, 1e-12))
            if standardize:
                x_mean = np.mean(x[None], axis=1)[0]
                x_std = np.std(x[None], axis=1)[0] + np.float32(1e-8)
                y_mean = np.float32(np.mean(y[None], axis=1)[0])
                y_std = np.float32(np.std(y[None], axis=1)[0] + 1e-8)
            else:
                x_mean = np.zeros(x.shape[1], np.float32)
                x_std = np.ones(x.shape[1], np.float32)
                y_mean = np.float32(0.0)
                y_std = np.float32(1.0)
            xs_std.append(((x - x_mean) / x_std).astype(np.float32))
            ys_std.append(((y - y_mean) / y_std).astype(np.float32))
            metas.append(_fit_meta(x_mean, x_std, y_mean, y_std, eps, C))
        Xp = np.zeros((B, n_max, d), np.float32)
        Yp = np.zeros((B, n_max), np.float32)
        mask = np.zeros((B, n_max), bool)
        for i, (xs, ys) in enumerate(zip(xs_std, ys_std)):
            Xp[i, : ns[i]] = xs
            Yp[i, : ns[i]] = ys
            mask[i, : ns[i]] = True

    # the compute hotspot: every training set's Gram block in ONE call
    with obs.span("svr.fit_exact", cat="svr", batch=B, n_max=n_max):
        K = _gram_batched(jnp.asarray(Xp), jnp.asarray(Xp), gamma, impl)
        ragged = not mask.all()
        K64 = np.asarray(K, np.float64)
        if ragged:  # zero the padded Gram rows/cols (pads are not real)
            K64 *= mask[:, :, None] & mask[:, None, :]
        C_s = np.asarray([m[5] for m in metas], np.float64)
        eps_s = np.asarray([m[4] for m in metas], np.float64)

        beta, bias = _solve_dual_ladder(
            K64, np.asarray(Yp, np.float64), C_s, eps_s, mask, ridge
        )

    if iters > 0:
        K32 = jnp.asarray(K)
        if ragged:
            K32 = K32 * (mask[:, :, None] & mask[:, None, :])
        beta_r = _ista_refine_batch(
            K32,
            jnp.asarray(Yp),
            jnp.asarray(beta, jnp.float32),
            jnp.asarray(C_s, jnp.float32),
            jnp.asarray(eps_s, jnp.float32),
            jnp.asarray(mask),
            iters=iters,
        )
        bias_r = np.asarray(
            jax.vmap(_recover_bias_masked)(
                K32,
                jnp.asarray(Yp),
                beta_r,
                jnp.asarray(C_s, jnp.float32),
                jnp.asarray(eps_s, jnp.float32),
                jnp.asarray(mask),
            ),
            np.float64,
        )
        beta = np.asarray(beta_r, np.float64)
        # only accept the polished bias where it stays sane (the polish can't
        # worsen the dual objective, but bias recovery on a degenerate free
        # set can); otherwise keep the active-set KKT bias.
        sane = np.isfinite(bias_r) & (np.abs(bias_r - bias) <= 1.0)
        bias = np.where(sane, bias_r, bias)

    models = []
    for i in range(B):
        x_mean, x_std, y_mean, y_std, _, _ = metas[i]
        models.append(
            SVRParams(
                # plain numpy: converted lazily at the first predict — eager
                # per-model device_puts here would dominate small-batch fits
                x_train=xs_std[i],
                beta=beta[i, : ns[i]].astype(np.float32),
                bias=float(bias[i]),
                gamma=gamma,
                x_mean=x_mean,
                x_std=x_std,
                y_mean=y_mean,
                y_std=y_std,
                log_target=log_target,
            )
        )
    return models


def fit(
    x: np.ndarray,
    y: np.ndarray,
    *,
    C: float = 10e3,
    gamma: float = 0.5,
    eps: float = 0.01,
    iters: int = 0,
    impl: Optional[str] = None,
    log_target: bool = False,
    standardize: bool = False,
    ridge: float = 1e-3,
) -> SVRParams:
    """Fit one ε-SVR step-time surface (paper §2.2).

    Args:
        x: (n, d) raw features — the paper's axes are (frequency GHz,
            active cores, input size).
        y: (n,) raw targets — measured execution times in seconds.
        C / gamma / eps: paper §3.4 hyper-parameters (defaults are the
            paper's grid-searched values; C and ε in raw-target seconds).

    Returns:
        ``SVRParams``; ``predict(params, x)`` returns seconds.

    Defaults are paper-faithful: RAW features and targets with γ = 0.5 and
    C = 10·10³ (the paper's grid-searched values act on raw (f, p, N) axes —
    γ = 0.5 is then local along cores/input-size and wide along frequency;
    standardizing first makes the kernel globally wide and the dual solve
    degenerate). ``standardize=True`` + ``log_target=True`` is the
    beyond-paper mode the TPU planner uses, whose features (chips, seq, batch)
    span orders of magnitude.

    Thin B = 1 wrapper over ``fit_many`` — single and batched fits share one
    numerical path (the ridge-escalated batched active-set solve).

    Example::

        import numpy as np
        from repro.core import svr
        x = np.array([[1.2, 4.0], [1.8, 8.0], [2.2, 16.0]], np.float32)
        y = np.array([4.0, 2.0, 1.0], np.float32)  # seconds
        model = svr.fit(x, y)
        assert svr.pae(model, x, y) < 0.2
    """
    return fit_many(
        [(x, y)],
        C=C,
        gamma=gamma,
        eps=eps,
        iters=iters,
        impl=impl,
        log_target=log_target,
        standardize=standardize,
        ridge=ridge,
    )[0]

def predict(params: SVRParams, x: np.ndarray, *, impl: Optional[str] = None):
    """Predict raw-unit targets for raw-unit features x: (m, d)."""
    if isinstance(params, rff_mod.RFFParams):
        return rff_mod.predict(params, x)
    xs = (jnp.asarray(x, jnp.float32) - params.x_mean) / params.x_std
    K = ops.rbf_gram(xs, params.x_train, params.gamma, impl=impl)
    ys = _matmul(K, params.beta) + params.bias
    out = ys * params.y_std + params.y_mean
    return jnp.exp(out) if params.log_target else out


def predict_many(
    models: Sequence[SVRParams], x: np.ndarray, *, impl: Optional[str] = None
):
    """Batched prediction: many fitted models over one shared query grid.

    The planning engine's hot path: all grid points of all pending workloads
    go through ONE ``rbf_gram`` call (batched leading dim) plus one batched
    matvec, instead of one Gram build per plan. Requires homogeneous models
    (same train-set shape / γ / target space) — the engine's per-family fits
    always are; heterogeneous inputs fall back to per-model ``predict``.
    Returns a list of per-model prediction arrays, aligned with ``models``.
    """
    models = list(models)  # materialize once: generators must not exhaust
    return predict_each(models, [x] * len(models), impl=impl)


def predict_each(
    models: Sequence[SVRParams],
    xs: Sequence[np.ndarray],
    *,
    impl: Optional[str] = None,
):
    """Batched prediction: model i evaluated on its OWN query set ``xs[i]``.

    The batched-characterization companion of ``predict_many`` (which shares
    one grid): used to score every freshly fitted family on its own training
    set in one ``rbf_gram`` call. Homogeneous models + same-shape queries
    batch; anything else falls back to per-model ``predict``.
    """
    models = list(models)
    if not models:
        return []
    if any(isinstance(m, rff_mod.RFFParams) for m in models):
        # RFF models have no Gram build to batch (the homogeneity check
        # below would also trip on the missing x_train); host matvecs for
        # an all-RFF batch, per-model dispatch for a mixed one.
        if all(isinstance(m, rff_mod.RFFParams) for m in models):
            return rff_mod.predict_each(models, xs)
        return [predict(m, q, impl=impl) for m, q in zip(models, xs)]
    m0 = models[0]
    q0 = np.shape(xs[0])
    homogeneous = all(
        m.x_train.shape == m0.x_train.shape
        and m.gamma == m0.gamma
        and m.log_target == m0.log_target
        for m in models[1:]
    ) and all(np.shape(q) == q0 for q in xs[1:])
    if not homogeneous:
        return [predict(m, q, impl=impl) for m, q in zip(models, xs)]
    Xs = jnp.stack(
        [(jnp.asarray(q, jnp.float32) - m.x_mean) / m.x_std
         for m, q in zip(models, xs)]
    )  # (B, m, d)
    Yt = jnp.stack([m.x_train for m in models])  # (B, n, d)
    K = ops.rbf_gram(Xs, Yt, m0.gamma, impl=impl)  # (B, m, n) — one call
    out = _predict_from_gram(
        K,
        jnp.stack([m.beta for m in models]),
        jnp.asarray([m.bias for m in models], jnp.float32),
        jnp.asarray([m.y_mean for m in models], jnp.float32),
        jnp.asarray([m.y_std for m in models], jnp.float32),
        m0.log_target,
    )
    return list(out)


def _predict_from_gram(K, beta, bias, y_mean, y_std, log_target: bool):
    # deliberately eager: the matvec is tiny and batch sizes vary call to
    # call — a jit here would recompile per batch size
    ys = jnp.einsum("bmn,bn->bm", K, beta, precision=_HIGHEST)
    ys = ys + bias[:, None]
    out = ys * y_std[:, None] + y_mean[:, None]
    return jnp.exp(out) if log_target else out


def mae(params: SVRParams, x, y) -> float:
    return float(jnp.mean(jnp.abs(predict(params, x) - jnp.asarray(y))))


def pae_from_pred(pred, y) -> float:
    """Percentage absolute error from precomputed predictions — the one
    definition shared by ``pae``, the engine's batched characterization
    scoring and the fleet's re-characterization path."""
    y = np.asarray(y, np.float64)
    return float(np.mean(np.abs(np.asarray(pred, np.float64) - y) / np.maximum(y, 1e-9)))


def pae(params: SVRParams, x, y) -> float:
    """Percentage absolute error (paper Table 1 metric)."""
    return pae_from_pred(predict(params, x), y)


def kfold_cv(
    x: np.ndarray,
    y: np.ndarray,
    *,
    k: int = 10,
    C: float = 10e3,
    gamma: float = 0.5,
    eps: float = 0.01,
    iters: int = 0,
    seed: int = 0,
    log_target: bool = False,
    standardize: bool = False,
):
    """Paper §3.4: k-fold cross validation, returns mean (MAE, PAE)."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    n = x.shape[0]
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    folds = np.array_split(order, k)
    maes, paes = [], []
    for i in range(k):
        test_idx = folds[i]
        train_idx = np.concatenate([folds[j] for j in range(k) if j != i])
        m = fit(
            x[train_idx],
            y[train_idx],
            C=C,
            gamma=gamma,
            eps=eps,
            iters=iters,
            log_target=log_target,
            standardize=standardize,
        )
        maes.append(mae(m, x[test_idx], y[test_idx]))
        paes.append(pae(m, x[test_idx], y[test_idx]))
    return float(np.mean(maes)), float(np.mean(paes))


def grid_search(
    x,
    y,
    *,
    Cs=(1e2, 1e3, 10e3),
    gammas=(0.1, 0.5, 1.0),
    eps: float = 0.01,
    k: int = 5,
    iters: int = 0,
):
    """Paper §3.4's hyper-parameter grid search (by CV PAE)."""
    best = None
    for C in Cs:
        for g in gammas:
            _, p = kfold_cv(x, y, k=k, C=C, gamma=g, eps=eps, iters=iters)
            if best is None or p < best[0]:
                best = (p, C, g)
    return {"pae": best[0], "C": best[1], "gamma": best[2]}
