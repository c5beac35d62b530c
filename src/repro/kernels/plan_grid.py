"""Pallas TPU kernels: the fused planning-grid sweep of ``core/engine.py``.

A planning round evaluates, for every pending workload, the objective
metric (W·T)·T^k over the shared (frequency × cores) grid, masks the
points its ``Constraints`` forbid, and takes either the argmin (plan) or
the pareto keep-set (frontier). At 10^4-10^5 workloads the unfused path
pays one host argmin + mask build per workload; these kernels do the
whole (B, G) sweep — metric build, masking, reduction — in one pass,
with the metric expression ordered exactly like the engine's objective
tensor so the chosen (f, cores) configs stay bitwise identical.

Layout: the grid is flattened C-order to G = nf·nc and padded to the
128-lane width (``tpu_space()``: 66 -> 128, ``cpu_space()``: 352 -> 384),
and B is padded to the 8-row block; each program instance holds its full
(8, G) slab in VMEM. The argmin kernel reduces over lanes with the
min/iota trick (first-minimum tie-break, ``np.argmin`` semantics); the
frontier kernel sweeps its 8 rows one at a time, each materializing one
(G, G) pairwise dominance matrix (576 KiB of f32 at G = 384, so a few
live intermediates stay well inside the scoped VMEM limit).

Reference oracles: ``ref.plan_argmin_ref`` / ``ref.pareto_mask_ref``
(the CPU compute path and the interpret-mode test ground truth),
dispatched by ``ops.py`` like every other kernel in this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _plan_argmin_kernel(t_ref, w_ref, k_ref, m_ref, o_ref, *, time_floor: float):
    t = jnp.maximum(t_ref[...], jnp.float32(time_floor))  # (bb, G)
    e = w_ref[...] * t  # (1, G) * (bb, G)
    metric = e * t ** k_ref[:, :1]  # VPU pow; k col 0 broadcast over lanes
    masked = jnp.where(m_ref[...] > 0.0, metric, jnp.float32(jnp.inf))
    mn = jnp.min(masked, axis=1, keepdims=True)  # (bb, 1)
    g = masked.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    idx = jnp.min(jnp.where(masked == mn, lanes, g), axis=1, keepdims=True)
    o_ref[...] = jnp.broadcast_to(idx, o_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("time_floor", "block_b", "interpret")
)
def plan_argmin_pallas(
    t: jnp.ndarray,  # (B, G) step times
    w: jnp.ndarray,  # (1, G) shared power grid
    k: jnp.ndarray,  # (B,)   objective exponents
    mask: jnp.ndarray,  # (B, G) feasibility as 0/1 float
    *,
    time_floor: float,
    block_b: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """First flat index of the masked objective minimum -> (B,) int32."""
    b, g = t.shape
    bb = block_b
    pad_b = (-b) % bb
    pad_g = (-g) % 128
    # padded lanes carry mask 0 -> +inf metric; padded rows are sliced off
    tp = jnp.pad(t.astype(jnp.float32), ((0, pad_b), (0, pad_g)), constant_values=1.0)
    wp = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad_g)), constant_values=1.0)
    mp = jnp.pad(mask.astype(jnp.float32), ((0, pad_b), (0, pad_g)))
    bp, gp = tp.shape
    # k rides in as a (bp, 128) lane-replicated slab: scalars-per-row in
    # SMEM would need a (1, 1) spec per row; replication is 512 B/row.
    kp = jnp.pad(k.astype(jnp.float32), (0, pad_b))
    k2 = jnp.broadcast_to(kp[:, None], (bp, 128))

    out = pl.pallas_call(
        functools.partial(_plan_argmin_kernel, time_floor=time_floor),
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
            pl.BlockSpec((1, gp), lambda i: (0, 0)),
            pl.BlockSpec((bb, 128), lambda i: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 128), jnp.int32),
        interpret=interpret,
    )(tp, wp, k2, mp)
    return out[:b, 0]


def _column(row: jnp.ndarray, diag: jnp.ndarray) -> jnp.ndarray:
    """(1, G) lane vector -> (G, 1) sublane vector, exactly: keep the
    diagonal of its sublane broadcast and sum across lanes (every other
    term is an exact 0). Mosaic refuses the (1, G) -> (G, 1) reshape."""
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _pareto_mask_kernel(t_ref, e_ref, m_ref, o_ref):
    bb, g = t_ref.shape
    iq = jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)  # q down the sublanes
    ip = jax.lax.broadcasted_iota(jnp.int32, (g, g), 1)  # p across the lanes
    diag = iq == ip
    earlier = iq < ip

    def row(r, carry):
        t = t_ref[pl.ds(r, 1), :]  # (1, G)
        e = e_ref[pl.ds(r, 1), :]
        feas = (m_ref[pl.ds(r, 1), :] > 0.0) & jnp.isfinite(t) & jnp.isfinite(e)
        tq = _column(t, diag)
        eq = _column(e, diag)
        fq = _column(jnp.where(feas, 1.0, 0.0), diag) > 0.0
        beats = fq & (
            ((tq < t) & (eq <= e))
            | ((tq == t) & (eq < e))
            | ((tq == t) & (eq == e) & earlier)
        )
        dominated = jnp.max(jnp.where(beats, 1.0, 0.0), axis=0, keepdims=True) > 0.0
        o_ref[pl.ds(r, 1), :] = jnp.where(feas & ~dominated, 1, 0).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, bb, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pareto_mask_pallas(
    t: jnp.ndarray,  # (B, G) step times
    e: jnp.ndarray,  # (B, G) energies
    mask: jnp.ndarray,  # (B, G) feasibility as 0/1 float
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pareto keep-set per batch row -> (B, G) bool.

    One program per 8 rows, the f32 sublane tile (B padded to a multiple,
    like ``plan_argmin_pallas``); the rows of a block are swept one at a
    time so only one (G, G) dominance matrix is live in VMEM."""
    b, g = t.shape
    bb = 8
    pad_b = (-b) % bb
    pad_g = (-g) % 128
    # padded lanes and rows carry mask 0 -> never kept; padded rows sliced off
    pads = ((0, pad_b), (0, pad_g))
    tp = jnp.pad(t.astype(jnp.float32), pads, constant_values=1.0)
    ep = jnp.pad(e.astype(jnp.float32), pads, constant_values=1.0)
    mp = jnp.pad(mask.astype(jnp.float32), pads)
    bp, gp = tp.shape

    out = pl.pallas_call(
        _pareto_mask_kernel,
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, gp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, gp), jnp.int32),
        interpret=interpret,
    )(tp, ep, mp)
    return out[:b, :g] > 0
