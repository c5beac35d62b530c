"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth for kernel tests (interpret=True vs ref allclose)
AND the compute path used on CPU / in the dry-run lowering (dispatched by
``ops.py``): they are written to be memory-lean (chunked online-softmax
attention, chunked SSD) so that 32k-prefill / 500k-decode dry-runs have sane
per-device footprints.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# RBF Gram matrix (the SVR hotspot of the paper's methodology)
# ---------------------------------------------------------------------------


def rbf_gram_ref(x: jnp.ndarray, y: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """K[i, j] = exp(-gamma * ||x_i - y_j||^2).   x: (n, d), y: (m, d)."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)  # not 1 bf16 pass
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    return jnp.exp(-gamma * d2)


# ---------------------------------------------------------------------------
# Fused planning-grid sweep (the engine's argmin / frontier hot path)
# ---------------------------------------------------------------------------


def plan_argmin_ref(
    t: jnp.ndarray,  # (B, G) step times, G = nf * nc flattened C-order
    w: jnp.ndarray,  # (1, G) shared power grid
    k: jnp.ndarray,  # (B,)   per-workload objective exponent
    mask: jnp.ndarray,  # (B, G) feasibility (bool or 0/1 float)
    *,
    time_floor: float,
) -> jnp.ndarray:
    """First flat index of the masked objective minimum, per batch row.

    Fuses what ``core/engine.py`` historically ran as separate ops: the
    metric tensor (W·T)·T^k, the constraint mask, and the argmin. The
    expression order matches the engine's objective tensor exactly so the
    f32 metric values — and therefore the chosen (f, cores) configs — are
    bitwise identical to the unfused path. Ties break to the FIRST flat
    index (``np.argmin`` semantics); an all-masked row returns 0 (callers
    detect emptiness host-side and take the infeasible fallback).
    """
    t = jnp.maximum(t.astype(jnp.float32), jnp.float32(time_floor))
    e = w.astype(jnp.float32) * t
    metric = e * t ** k.astype(jnp.float32)[:, None]
    masked = jnp.where(mask > 0, metric, jnp.float32(jnp.inf))
    return jnp.argmin(masked, axis=1).astype(jnp.int32)


def pareto_mask_ref(
    t: jnp.ndarray,  # (B, G) step times
    e: jnp.ndarray,  # (B, G) energies
    mask: jnp.ndarray,  # (B, G) feasibility (bool or 0/1 float)
) -> jnp.ndarray:
    """Pareto-frontier membership per batch row (bool, shape (B, G)).

    A point survives iff it is feasible, finite in both axes, and no other
    feasible point weakly dominates it — with the same deterministic
    tie-break as ``engine.pareto_frontier`` (equal (t, e) pairs keep only
    the lowest flat index). The O(G^2) pairwise test is algebraically
    identical to the host lexsort + cummin sweep: a point is dropped there
    iff some point sorted strictly before it has energy <= its own, which
    is exactly the dominance predicate below.
    """
    feas = (mask > 0) & jnp.isfinite(t) & jnp.isfinite(e)
    tq, tp = t[:, :, None], t[:, None, :]  # q on axis 1, p on axis 2
    eq, ep = e[:, :, None], e[:, None, :]
    g = t.shape[1]
    iq = jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)[None]
    ip = jax.lax.broadcasted_iota(jnp.int32, (g, g), 1)[None]
    beats = feas[:, :, None] & (
        ((tq < tp) & (eq <= ep))
        | ((tq == tp) & (eq < ep))
        | ((tq == tp) & (eq == ep) & (iq < ip))
    )
    return feas & ~jnp.any(beats, axis=1)


# ---------------------------------------------------------------------------
# Flash attention (chunked online softmax; causal / sliding-window / full)
# ---------------------------------------------------------------------------


def _attn_mask(
    q_pos: jnp.ndarray,  # (bq,)
    k_pos: jnp.ndarray,  # (bk,)
    causal: bool,
    window: Optional[int],
    kv_len: Optional[int],
) -> jnp.ndarray:
    """True where attention is allowed. Shape (bq, bk)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        # sliding window: key within the last `window` positions of the query
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    if kv_len is not None:
        m &= k_pos[None, :] < kv_len
    return m


def flash_attention_ref(
    q: jnp.ndarray,  # (b, h, sq, d)
    k: jnp.ndarray,  # (b, hk, skv, d)
    v: jnp.ndarray,  # (b, hk, skv, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 512,
    return_lse: bool = False,
):
    """Memory-lean multi-head attention with GQA (hk | h) support.

    Never materializes the (sq, skv) score matrix: nested scan over q-chunks
    (outer) and kv-chunks (inner) with an online-softmax carry. ``q_offset``
    positions queries at ``q_offset..q_offset+sq`` for decode steps.
    ``return_lse`` additionally returns the log-sum-exp statistics
    (b, h, sq) needed by the memory-efficient backward.

    NOTE: differentiating this function directly makes jax save every
    (bq, bk) probability chunk across both scans — O(S^2) residuals. Always
    differentiate through ``ops.flash_attention``, which pairs it with
    ``flash_attention_bwd_ref`` (O(S) residuals).
    """
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    assert h % hk == 0, (h, hk)
    groups = h // hk
    if scale is None:
        scale = 1.0 / (d**0.5)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    # pad seq dims to chunk multiples
    pq = (-sq) % bq
    pk = (-skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk
    eff_kv_len = skv if (pk or kv_len is not None) else None
    if kv_len is not None:
        eff_kv_len = kv_len

    # (b, hk, g, nq, bq, d)
    qs = qp.reshape(b, hk, groups, nq, bq, d)
    ks = kp.reshape(b, hk, nk, bk, d)
    vs = vp.reshape(b, hk, nk, bk, d)

    def q_chunk(iq, q_blk):
        # q_blk: (b, hk, g, bq, d)
        q_pos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, ik_blk):
            acc, m, l = carry
            ik, k_blk, v_blk = ik_blk
            k_pos = ik * bk + jnp.arange(bk)
            s = jnp.einsum(
                "bkgqd,bkcd->bkgqc", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale
            mask = _attn_mask(q_pos, k_pos, causal, window, eff_kv_len)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard -inf - -inf
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkgqc,bkcd->bkgqd", p, v_blk, preferred_element_type=jnp.float32
            )
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, hk, groups, bq, d), jnp.float32)
        m0 = jnp.full((b, hk, groups, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hk, groups, bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step,
            (acc0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(ks, 2, 0), jnp.moveaxis(vs, 2, 0)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # lse = m + log(l): exp(s - lse) reproduces the final probabilities
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        lse = jnp.where(
            l > 0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf
        )
        return out.astype(q.dtype), lse

    # scan over q chunks (outer), moving the chunk axis to the front
    qs_t = jnp.moveaxis(qs, 3, 0)  # (nq, b, hk, g, bq, d)
    outs, lses = jax.lax.map(lambda args: q_chunk(*args), (jnp.arange(nq), qs_t))
    out = jnp.moveaxis(outs, 0, 3).reshape(b, hk, groups, nq * bq, d)
    out = out[..., :sq, :].reshape(b, h, sq, d)
    if return_lse:
        lse = jnp.moveaxis(lses, 0, 3).reshape(b, hk, groups, nq * bq)
        lse = lse[..., :sq].reshape(b, h, sq)
        return out, lse
    return out


def flash_attention_bwd_ref(
    q,  # (b, h, sq, d)
    k,  # (b, hk, skv, d)
    v,  # (b, hk, skv, d)
    out,  # (b, h, sq, d)
    lse,  # (b, h, sq) f32
    dout,  # (b, h, sq, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 512,
):
    """Flash-attention backward with O(S) residual memory.

    Recomputes probability chunks from (q, k, lse) and accumulates
    dq/dk/dv chunkwise (Dao et al. alg. 2): no (sq, skv) tensor and no
    AD-saved per-chunk residuals ever exist. This is what makes the 32k
    training cells fit a 16 GB chip.
    """
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    groups = h // hk
    if scale is None:
        scale = 1.0 / (d**0.5)
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pq = (-sq) % bq
    pk = (-skv) % bk
    pad4 = lambda x, p: jnp.pad(x, ((0, 0), (0, 0), (0, p), (0, 0))) if p else x
    qp, op_, dop = pad4(q, pq), pad4(out, pq), pad4(dout, pq)
    kp, vp = pad4(k, pk), pad4(v, pk)
    lsep = (
        jnp.pad(lse, ((0, 0), (0, 0), (0, pq)), constant_values=jnp.inf)
        if pq
        else lse
    )
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk
    eff_kv_len = kv_len if kv_len is not None else (skv if pk else None)

    # grouped layouts
    qg = jnp.moveaxis(qp.reshape(b, hk, groups, nq, bq, d), 3, 0)
    og = jnp.moveaxis(op_.reshape(b, hk, groups, nq, bq, d), 3, 0)
    dog = jnp.moveaxis(dop.reshape(b, hk, groups, nq, bq, d), 3, 0)
    lseg = jnp.moveaxis(lsep.reshape(b, hk, groups, nq, bq), 3, 0)
    Dg = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1)
    ks_ = jnp.moveaxis(kp.reshape(b, hk, nk, bk, d), 2, 0)
    vs_ = jnp.moveaxis(vp.reshape(b, hk, nk, bk, d), 2, 0)

    def kv_chunk(dq_acc, jk_blk):
        jk, k_blk, v_blk = jk_blk
        k_pos = jk * bk + jnp.arange(bk)

        def q_step(carry, iq_blk):
            dk_j, dv_j = carry
            iq, q_blk, do_blk, lse_blk, D_blk = iq_blk
            q_pos = q_offset + iq * bq + jnp.arange(bq)
            s = (
                jnp.einsum(
                    "bkgqd,bkcd->bkgqc",
                    q_blk,
                    k_blk,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            mask = _attn_mask(q_pos, k_pos, causal, window, eff_kv_len)
            lse_safe = jnp.where(jnp.isfinite(lse_blk), lse_blk, 0.0)
            p = jnp.where(mask[None, None, None], jnp.exp(s - lse_safe[..., None]), 0.0)
            # keep the GQA group axis g UNREDUCED in the dk/dv carries: g is
            # the tensor-parallel-sharded axis, and contracting it inside the
            # scan forces a partial-sum all-reduce EVERY (q-chunk, kv-chunk)
            # iteration; deferring the sum to after both scans leaves one
            # all-reduce per attention call (16-64x fewer collective bytes;
            # EXPERIMENTS.md §Perf).
            dv_j = dv_j + jnp.einsum(
                "bkgqc,bkgqd->bkgcd", p, do_blk.astype(jnp.float32)
            )
            dp = jnp.einsum(
                "bkgqd,bkcd->bkgqc",
                do_blk,
                v_blk,
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - D_blk[..., None]) * scale
            dq_i = jnp.einsum("bkgqc,bkcd->bkgqd", ds, k_blk.astype(jnp.float32))
            dk_j = dk_j + jnp.einsum(
                "bkgqc,bkgqd->bkgcd", ds, q_blk.astype(jnp.float32)
            )
            return (dk_j, dv_j), dq_i

        zeros_kv = jnp.zeros((b, hk, groups, bk, d), jnp.float32)
        (dk_j, dv_j), dq_contrib = jax.lax.scan(
            q_step,
            (zeros_kv, zeros_kv),
            (jnp.arange(nq), qg, dog, lseg, Dg),
        )
        return dq_acc + dq_contrib, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, b, hk, groups, bq, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        kv_chunk, dq0, (jnp.arange(nk), ks_, vs_)
    )
    dq = jnp.moveaxis(dq, 0, 3).reshape(b, hk, groups, nq * bq, d)[..., :sq, :]
    dq = dq.reshape(b, h, sq, d).astype(q.dtype)
    dks = dks.sum(axis=3)  # reduce groups once, after the scans
    dvs = dvs.sum(axis=3)
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, hk, nk * bk, d)[..., :skv, :].astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, hk, nk * bk, d)[..., :skv, :].astype(v.dtype)
    return dq, dk, dv


def mha_naive_ref(
    q, k, v, *, causal=True, window=None, scale=None, q_offset=0, kv_len=None
):
    """O(s^2)-memory oracle used only in tests against small shapes."""
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    groups = h // hk
    if scale is None:
        scale = 1.0 / (d**0.5)
    kq = jnp.repeat(k, groups, axis=1)
    vq = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("bhqd,bhcd->bhqc", q.astype(jnp.float32), kq.astype(jnp.float32))
    s = s * scale
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    mask = _attn_mask(q_pos, k_pos, causal, window, kv_len)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqc,bhcd->bhqd", p, vq.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) chunked scan
# ---------------------------------------------------------------------------


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} a[..., k] for j<i,
    0 on the diagonal, -inf above. a: (..., T)."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum_{j+1..i} when i>=j
    idx = jnp.arange(T)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan_ref(
    x: jnp.ndarray,  # (b, s, h, p)   inputs (already multiplied by nothing)
    dt: jnp.ndarray,  # (b, s, h)      positive step sizes
    A: jnp.ndarray,  # (h,)           negative decay rates
    B: jnp.ndarray,  # (b, s, g, n)   input matrices (g groups, h % g == 0)
    C: jnp.ndarray,  # (b, s, g, n)   output matrices
    *,
    chunk: int = 128,
    h0: Optional[jnp.ndarray] = None,  # (b, h, n, p) initial state
    return_state: bool = False,
):
    """Chunked SSD as in Mamba2 ("Transformers are SSMs", arXiv:2405.21060).

    Recurrence: h_t = exp(A*dt_t) h_{t-1} + dt_t * B_t x_t^T ;  y_t = C_t h_t.
    Returns y: (b, s, h, p) [and final state (b, h, n, p)].
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert h % g == 0
    rep = h // g

    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S = x.shape[1]
    nc = S // chunk

    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = B.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Bh = jnp.repeat(Bc, rep, axis=3)  # (b, nc, T, h, n)
    Ch = jnp.repeat(Cc, rep, axis=3)

    a = dtc * A[None, None, None, :]  # (b, nc, T, h) log-decays (negative)
    a_cum = jnp.cumsum(a, axis=2)  # within-chunk cumulative

    # --- intra-chunk (quadratic attention-like) term
    L = jnp.exp(_segsum(jnp.moveaxis(a, 2, -1)))  # (b, nc, h, T, T)
    CB = jnp.einsum("bcthn,bcshn->bchts", Ch, Bh)  # (b, nc, h, T, S)
    M = CB * L
    y_intra = jnp.einsum("bchts,bcsh,bcshp->bcthp", M, dtc, xc)

    # --- chunk states: S_c = sum_t decay_to_end(t) dt_t B_t x_t
    decay_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)  # (b, nc, T, h)
    states = jnp.einsum("bcthn,bcth,bcth,bcthp->bchnp", Bh, decay_end, dtc, xc)

    # --- inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])  # (b, nc, h) total decay of chunk

    def chunk_step(hprev, inp):
        st, dec = inp  # (b, h, n, p), (b, h)
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev

    h_init = (
        jnp.zeros((b, h, n, p), jnp.float32)
        if h0 is None
        else h0.astype(jnp.float32)
    )
    h_last, h_prevs = jax.lax.scan(
        chunk_step,
        h_init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (b, nc, h, n, p) state entering chunk

    # --- state contribution: y_state[t] = C_t · (decay_from_start(t) * h_prev)
    decay_in = jnp.exp(a_cum)  # (b, nc, T, h)
    y_state = jnp.einsum("bcthn,bcth,bchnp->bcthp", Ch, decay_in, h_prevs)

    y = (y_intra + y_state).reshape(b, S, h, p)[:, :s]
    y = y.astype(x.dtype)
    if return_state:
        return y, h_last.astype(jnp.float32)
    return y


def ssm_decode_step_ref(
    h: jnp.ndarray,  # (b, h, n, p) state
    x_t: jnp.ndarray,  # (b, h, p)
    dt_t: jnp.ndarray,  # (b, h)
    A: jnp.ndarray,  # (h,)
    B_t: jnp.ndarray,  # (b, g, n)
    C_t: jnp.ndarray,  # (b, g, n)
):
    """One recurrent SSD step (used by serve_step for SSM archs)."""
    b, hh, n, p = h.shape
    g = B_t.shape[1]
    rep = hh // g
    Bh = jnp.repeat(B_t, rep, axis=1).astype(jnp.float32)  # (b, h, n)
    Ch = jnp.repeat(C_t, rep, axis=1).astype(jnp.float32)
    dec = jnp.exp(dt_t.astype(jnp.float32) * A[None, :])  # (b, h)
    upd = dt_t[..., None, None].astype(jnp.float32) * Bh[..., :, None] * x_t[
        ..., None, :
    ].astype(jnp.float32)
    h_new = h * dec[..., None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", Ch, h_new)
    return h_new, y.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# int8 block quantization codec (gradient compression)
# ---------------------------------------------------------------------------


def int8_quantize_ref(x: jnp.ndarray, block: int = 256):
    """Blockwise symmetric int8 quantization of a flat vector.

    Returns (q: int8 (nb*block,), scales: f32 (nb,)). Input is padded to a
    block multiple (callers keep the original length)."""
    n = x.shape[0]
    pad = (-n) % block
    xf = jnp.pad(x.astype(jnp.float32), (0, pad))
    nb = xf.shape[0] // block
    xb = xf.reshape(nb, block)
    amax = jnp.max(jnp.abs(xb), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale


def int8_dequantize_ref(q: jnp.ndarray, scale: jnp.ndarray, n: int, block: int = 256):
    nb = scale.shape[0]
    x = q.reshape(nb, block).astype(jnp.float32) * scale[:, None]
    return x.reshape(-1)[:n]
