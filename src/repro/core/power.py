"""CMOS power model of the paper (Eq. 1-7) and its multi-linear fit.

The model treats the processor as a bag of CMOS gates:

    P_total = P_static + P_leak + P_dynamic           (Eq. 1)
    P_dynamic = C V^2 f,  P_leak ∝ V,  f ∝ V          (Eq. 2-4)
  ⇒ per-core: P(f) = c1 f^3 + c2 f + c3               (Eq. 5)
  ⇒ node:     P(f, p, s) = p (c1 f^3 + c2 f) + c3 + c4 s   (Eq. 7)

with f the clock (GHz), p the number of active cores (chips, on TPU), and s
the number of sockets (pods, on TPU).

The fit is ordinary least squares on the basis [p f^3, p f, 1, s] — the
paper's "multi-linear regression" — implemented in JAX via the normal
equations with a tiny Tikhonov damping for conditioning.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

# Paper Eq. (9): fit for the 2x Xeon E5-2698v3 node, f in GHz, P in watts.
PAPER_COEFFS = (0.29, 0.97, 198.59, 9.18)


@dataclasses.dataclass(frozen=True)
class PowerModel:
    """P(f, p, s) = p (c1 f^3 + c2 f) + c3 + c4 s."""

    c1: float
    c2: float
    c3: float
    c4: float

    def __call__(self, f, p, s):
        f = jnp.asarray(f, jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32)
        return p * (self.c1 * f**3 + self.c2 * f) + self.c3 + self.c4 * s

    def at(self, f: float, p: int, s: int) -> float:
        """Eq. 7 at one point, on the host: ``float(self(f, p, s))`` bit for
        bit, with no device program.

        NumPy scalars of the dtype ``__call__`` computes in, in its order of
        operations: each coefficient rounded to that dtype, ``f**3`` as
        ``f * (f * f)`` (how ``lax.integer_pow`` lowers a cube), and
        ``c4 * s`` formed in Python and rounded once, as JAX rounds a weakly
        typed Python scalar.
        """
        x = np.float64 if jax.config.read("jax_enable_x64") else np.float32
        f = x(f)
        return float(
            x(p) * (x(self.c1) * (f * (f * f)) + x(self.c2) * f) + x(self.c3) + x(self.c4 * s)
        )

    def dynamic_parcel(self, f, p, s):
        """p(c1 f^3 + c2 f) + c4 s — everything that scales with activity."""
        return p * (self.c1 * jnp.asarray(f) ** 3 + self.c2 * jnp.asarray(f)) + self.c4 * s

    def static_parcel(self):
        return self.c3

    def race_to_idle_expected(self, f_max: float, p_max: int, s_max: int) -> bool:
        """Paper §4.1: race-to-idle is optimal when even the maximal dynamic
        parcel stays below the static parcel."""
        return bool(self.dynamic_parcel(f_max, p_max, s_max) < self.static_parcel())

    def coeffs(self) -> tuple[float, float, float, float]:
        return (self.c1, self.c2, self.c3, self.c4)


def paper_power_model() -> PowerModel:
    return PowerModel(*PAPER_COEFFS)


def _design_matrix(f: jnp.ndarray, p: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    f = jnp.asarray(f, jnp.float32)
    p = jnp.asarray(p, jnp.float32)
    s = jnp.asarray(s, jnp.float32)
    return jnp.stack([p * f**3, p * f, jnp.ones_like(f), s], axis=-1)


@jax.jit
def _ols(X: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    # Minimum-norm least squares. The basis is tiny (4 columns), but it can
    # go rank-deficient on legitimate grids: a single-socket node's sweep
    # has s ≡ 1, making the [1, s] columns collinear — normal equations
    # blow up there (NaN coefficients) while lstsq splits c3/c4 into the
    # minimum-norm solution whose *predictions* are still exact.
    return jnp.linalg.lstsq(X, y)[0]


def fit_power_model(
    f: np.ndarray | jnp.ndarray,
    p: np.ndarray | jnp.ndarray,
    s: np.ndarray | jnp.ndarray,
    watts: np.ndarray | jnp.ndarray,
) -> PowerModel:
    """Fit Eq. (7) coefficients from (f, p, s) -> measured watts samples.

    Mirrors the paper §3.3: stress samples over the full (frequency x cores)
    grid, one OLS solve. Sockets enter through `s` (the paper always powers
    both sockets; we also fit single-socket samples when available so c4 is
    identified).
    """
    X = _design_matrix(jnp.asarray(f), jnp.asarray(p), jnp.asarray(s))
    beta = _ols(X, jnp.asarray(watts, jnp.float32))
    c1, c2, c3, c4 = (float(b) for b in beta)
    return PowerModel(c1, c2, c3, c4)


def absolute_percentage_error(model: PowerModel, f, p, s, watts) -> float:
    """Paper Eq. (10): mean |y - y_model| / y."""
    pred = model(jnp.asarray(f), jnp.asarray(p), jnp.asarray(s))
    y = jnp.asarray(watts, jnp.float32)
    return float(jnp.mean(jnp.abs(y - pred) / y))


def rmse(model: PowerModel, f, p, s, watts) -> float:
    pred = model(jnp.asarray(f), jnp.asarray(p), jnp.asarray(s))
    y = jnp.asarray(watts, jnp.float32)
    return float(jnp.sqrt(jnp.mean((y - pred) ** 2)))


def fit_report(model: PowerModel, f, p, s, watts) -> Mapping[str, float]:
    return {
        "c1": model.c1,
        "c2": model.c2,
        "c3": model.c3,
        "c4": model.c4,
        "ape": absolute_percentage_error(model, f, p, s, watts),
        "rmse_watts": rmse(model, f, p, s, watts),
    }
