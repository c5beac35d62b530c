"""Pallas TPU kernel: tiled RBF Gram matrix  K[i,j] = exp(-g ||x_i - y_j||^2).

This is the compute hotspot of the paper's methodology: both SVR training
(n x n Gram over the characterization samples) and batched prediction
(n_support x n_query) are Gram-bound, O(n m d). The kernel maps the cross
term x·yᵀ onto the MXU (128-aligned tiles) and the exp onto the VPU, keeping
one (bn, d) x-tile, one (bm, d) y-tile and the (bn, bm) output tile resident
in VMEM.

VMEM budget per program instance (defaults bn = bm = 128, d padded to 128):
  x tile 128x128 f32 (64 KiB) + y tile (64 KiB) + out (64 KiB)  « 16 MiB VMEM.
d is loaded un-tiled (characterization features are tiny: the paper's feature
vector is (f, p, N) -> d = 3; fleet-wide planners add a handful more), padded
to the 128 lane width outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rbf_gram_kernel(x_ref, y_ref, o_ref, *, gamma: float):
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    y = y_ref[...].astype(jnp.float32)  # (bm, d)
    # ||x - y||^2 = |x|^2 + |y|^2 - 2 x·yᵀ ; cross term on the MXU, at full
    # f32 precision: Mosaic's default is one bf16 pass, 5e-2 off in K on a v5e
    xy = jax.lax.dot_general(
        x,
        y,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    xx = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
    yy = jnp.sum(y * y, axis=1, keepdims=True).T  # (1, bm)
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    o_ref[...] = jnp.exp(-gamma * d2)


@functools.partial(
    jax.jit, static_argnames=("gamma", "block_n", "block_m", "interpret")
)
def rbf_gram_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    gamma: float,
    block_n: int = 128,
    block_m: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: (n, d), y: (m, d)  ->  K: (n, m) float32."""
    n, d = x.shape
    m, _ = y.shape
    bn = min(block_n, max(8, n))
    bm = min(block_m, max(128, min(m, 128)))
    pad_n = (-n) % bn
    pad_m = (-m) % bm
    pad_d = (-d) % 128
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, pad_m), (0, pad_d)))
    np_, mp_ = xp.shape[0], yp.shape[0]
    dp = xp.shape[1]

    grid = (np_ // bn, mp_ // bm)
    out = pl.pallas_call(
        functools.partial(_rbf_gram_kernel, gamma=gamma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:n, :m]
