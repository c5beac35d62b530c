"""jit'd public wrappers around the Pallas kernels, with backend dispatch.

Dispatch policy (``impl=`` on each call; None means "auto"):
  * "auto"              — Pallas on TPU backends, jnp reference elsewhere
                          (CPU dry-run / tests lower the reference path).
  * "pallas"            — force compiled Pallas (TPU).
  * "pallas_interpret"  — Pallas interpreter on CPU (kernel-correctness tests).
  * "ref"               — force the jnp oracle.

Differentiation: Pallas forwards are paired with recompute-based VJPs that
reuse the reference implementations — gradients are exact w.r.t. the oracle
semantics, and the kernels stay forward-only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.int8_codec import int8_dequantize_pallas, int8_quantize_pallas
from repro.kernels.plan_grid import pareto_mask_pallas, plan_argmin_pallas
from repro.kernels.rbf_gram import rbf_gram_pallas
from repro.kernels.ssd_scan import ssd_chunks_pallas


def resolve_impl(impl: Optional[str]) -> str:
    impl = impl or "auto"
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


# ---------------------------------------------------------------------------
# RBF Gram
# ---------------------------------------------------------------------------


def rbf_gram(x, y, gamma: float, *, impl: Optional[str] = None, block: int = 128):
    """K[i,j] = exp(-gamma ||x_i - y_j||^2); x (n,d), y (m,d) -> (n,m) f32.

    Also accepts a batch dim — x (b,n,d), y (b,m,d) -> (b,n,m) — so callers
    (``svr.predict_many``) can evaluate many Gram blocks in one call.
    """
    mode = resolve_impl(impl)
    if jnp.ndim(x) == 3:
        if mode == "ref":
            return jax.vmap(lambda a, b: ref.rbf_gram_ref(a, b, gamma))(x, y)
        return jax.vmap(
            lambda a, b: rbf_gram_pallas(
                a,
                b,
                gamma=gamma,
                block_n=block,
                block_m=block,
                interpret=(mode == "pallas_interpret"),
            )
        )(x, y)
    if mode == "ref":
        return ref.rbf_gram_ref(x, y, gamma)
    return rbf_gram_pallas(
        x,
        y,
        gamma=gamma,
        block_n=block,
        block_m=block,
        interpret=(mode == "pallas_interpret"),
    )


# ---------------------------------------------------------------------------
# Fused planning-grid sweep (engine argmin / frontier)
# ---------------------------------------------------------------------------


def plan_argmin(
    t, w, k, mask, *, time_floor: float, impl: Optional[str] = None
):
    """Masked objective argmin per batch row; t (B, G), w (G,)/(1, G),
    k (B,), mask (B, G) -> (B,) int32 first-minimum flat indices.

    Fuses the engine's metric build ((W·T)·T^k, T floored), constraint
    masking and argmin. The f32 metric matches ``engine._objective``'s
    expression order bitwise, and ties break to the first flat index —
    ``np.argmin`` over the unfused tensor picks the identical config.
    """
    mode = resolve_impl(impl)
    t = jnp.asarray(t, jnp.float32)
    w2 = jnp.asarray(w, jnp.float32).reshape(1, -1)
    k = jnp.asarray(k, jnp.float32)
    m = jnp.asarray(mask)
    if mode == "ref":
        return ref.plan_argmin_ref(t, w2, k, m, time_floor=time_floor)
    return plan_argmin_pallas(
        t,
        w2,
        k,
        m.astype(jnp.float32),
        time_floor=float(time_floor),
        interpret=(mode == "pallas_interpret"),
    )


def pareto_mask(t, e, mask, *, impl: Optional[str] = None):
    """Pareto keep-set per batch row; t, e, mask (B, G) -> (B, G) bool.

    Same dominance semantics (and flat-index tie-break) as the host
    ``engine.pareto_frontier`` lexsort + cummin sweep; non-finite or
    masked-out points never survive.
    """
    mode = resolve_impl(impl)
    t = jnp.asarray(t, jnp.float32)
    e = jnp.asarray(e, jnp.float32)
    m = jnp.asarray(mask)
    if mode == "ref":
        return ref.pareto_mask_ref(t, e, m)
    return pareto_mask_pallas(
        t, e, m.astype(jnp.float32), interpret=(mode == "pallas_interpret")
    )


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_vjp(
    causal, window, scale, q_offset, kv_len, block_q, block_k, mode
):
    """custom_vjp-wrapped flash attention for one static config.

    Used for BOTH the Pallas and the jnp-reference forward: differentiating
    the reference directly makes jax save every probability chunk across the
    nested scans (O(S^2) residuals — 63 GB/device on a 32-layer 4k cell).
    The backward here is the chunked recompute (``flash_attention_bwd_ref``)
    with O(S) residuals: (q, k, v) only.
    """
    kw = dict(
        causal=causal,
        window=window,
        scale=scale,
        q_offset=q_offset,
        kv_len=kv_len,
        block_q=block_q,
        block_k=block_k,
    )

    def pallas_fwd(q, k, v):
        b, h, sq, d = q.shape
        _, hk, skv, _ = k.shape
        groups = h // hk
        kx = jnp.repeat(k, groups, axis=1) if groups > 1 else k
        vx = jnp.repeat(v, groups, axis=1) if groups > 1 else v
        out = flash_attention_pallas(
            q.reshape(b * h, sq, d),
            kx.reshape(b * h, skv, d),
            vx.reshape(b * h, skv, d),
            interpret=(mode == "pallas_interpret"),
            **kw,
        )
        return out.reshape(b, h, sq, d)

    def fwd_impl(q, k, v):
        if mode == "ref":
            return ref.flash_attention_ref(q, k, v, **kw)
        return pallas_fwd(q, k, v)

    @jax.custom_vjp
    def f(q, k, v):
        return fwd_impl(q, k, v)

    def fwd(q, k, v):
        return fwd_impl(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        # recompute (out, lse) memory-lean, then chunked backward
        out, lse = ref.flash_attention_ref(q, k, v, return_lse=True, **kw)
        dq, dk, dv = ref.flash_attention_bwd_ref(
            q, k, v, out, lse, g.astype(q.dtype), **kw
        )
        return dq, dk, dv

    f.defvjp(fwd, bwd)
    return f


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len=None,
    block_q: int = 512,
    block_k: int = 512,
    impl: Optional[str] = None,
):
    """Multi-head attention, GQA-aware. q (b,h,sq,d), k/v (b,hk,skv,d).

    ``kv_len`` may be a traced array (decode with a ring cache); that always
    routes to the reference path (the decode gather is memory-bound — a
    Pallas kernel buys nothing there).
    """
    mode = resolve_impl(impl)
    dynamic_len = kv_len is not None and not isinstance(kv_len, int)
    dynamic_off = not isinstance(q_offset, int)
    if dynamic_len or dynamic_off:
        # decode path (traced cache lengths): inference-only, no vjp needed
        return ref.flash_attention_ref(
            q,
            k,
            v,
            causal=causal,
            window=window,
            scale=scale,
            q_offset=q_offset,
            kv_len=kv_len,
            block_q=block_q,
            block_k=block_k,
        )
    f = _flash_vjp(
        causal, window, scale, q_offset, kv_len, block_q, block_k, mode
    )
    return f(q, k, v)


# ---------------------------------------------------------------------------
# Mamba2 SSD scan
# ---------------------------------------------------------------------------


def _ssd_pallas_impl(x, dt, A, B, C, *, chunk, h0, return_state, interpret):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S = x.shape[1]
    nc = S // chunk

    # (b*h, nc, T, ·) layouts for the kernel
    xc = jnp.moveaxis(x, 2, 1).reshape(b * h, nc, chunk, p)
    dtc = jnp.moveaxis(dt, 2, 1).reshape(b * h, nc, chunk)
    Bh = jnp.repeat(B, rep, axis=2) if rep > 1 else B
    Ch = jnp.repeat(C, rep, axis=2) if rep > 1 else C
    Bc = jnp.moveaxis(Bh, 2, 1).reshape(b * h, nc, chunk, n)
    Cc = jnp.moveaxis(Ch, 2, 1).reshape(b * h, nc, chunk, n)
    a = dtc * jnp.tile(A, b)[:, None, None]

    y_intra, states, c_decay, chunk_decay = ssd_chunks_pallas(
        xc.astype(jnp.float32),
        dtc.astype(jnp.float32),
        a.astype(jnp.float32),
        Bc.astype(jnp.float32),
        Cc.astype(jnp.float32),
        chunk=chunk,
        interpret=interpret,
    )

    # inter-chunk recurrence (sequential over nc, tiny)
    h_init = (
        jnp.zeros((b * h, n, p), jnp.float32)
        if h0 is None
        else h0.reshape(b * h, n, p).astype(jnp.float32)
    )

    def step(hprev, inp):
        st, dec = inp  # (bh, n, p), (bh, 1, 1)
        return hprev * dec + st, hprev

    h_last, h_prevs = jax.lax.scan(
        step,
        h_init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (bh, nc, n, p)
    y_state = jnp.einsum("kctn,kcnp->kctp", c_decay, h_prevs)
    y = (y_intra + y_state).reshape(b, h, nc * chunk, p)
    y = jnp.moveaxis(y, 1, 2)[:, :s].astype(x.dtype)
    if return_state:
        return y, h_last.reshape(b, h, n, p)
    return y


@functools.lru_cache(maxsize=None)
def _ssd_vjp(chunk, return_state, mode):
    """custom_vjp for SSD — used for the REF path too: differentiating the
    chunked reference directly lets AD save the (T, T) intra-chunk decay/
    probability tensors of EVERY layer across the layer scan; the recompute
    VJP keeps residuals to (x, dt, A, B, C) so only the layer under
    backward holds its chunk tensors (transiently)."""
    ref_fn = functools.partial(
        ref.ssd_scan_ref, chunk=chunk, return_state=return_state
    )

    def fwd_impl(x, dt, A, B, C):
        if mode == "ref":
            return ref_fn(x, dt, A, B, C)
        return _ssd_pallas_impl(
            x,
            dt,
            A,
            B,
            C,
            chunk=chunk,
            h0=None,
            return_state=return_state,
            interpret=(mode == "pallas_interpret"),
        )

    @jax.custom_vjp
    def f(x, dt, A, B, C):
        return fwd_impl(x, dt, A, B, C)

    def fwd(x, dt, A, B, C):
        return fwd_impl(x, dt, A, B, C), (x, dt, A, B, C)

    def bwd(res, g):
        _, vjp = jax.vjp(ref_fn, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def ssd_scan(
    x,
    dt,
    A,
    B,
    C,
    *,
    chunk: int = 128,
    h0=None,
    return_state: bool = False,
    impl: Optional[str] = None,
):
    """Chunked Mamba2 SSD. See ``ref.ssd_scan_ref`` for semantics."""
    mode = resolve_impl(impl)
    if h0 is not None:  # decode/prefill state threading — inference only
        return ref.ssd_scan_ref(
            x, dt, A, B, C, chunk=chunk, h0=h0, return_state=return_state
        )
    f = _ssd_vjp(chunk, return_state, mode)
    return f(x, dt, A, B, C)


ssm_decode_step = ref.ssm_decode_step_ref  # recurrent step is pure jnp


# ---------------------------------------------------------------------------
# int8 codec
# ---------------------------------------------------------------------------


def int8_quantize(x, *, block: int = 256, impl: Optional[str] = None):
    mode = resolve_impl(impl)
    if mode == "ref":
        return ref.int8_quantize_ref(x, block=block)
    return int8_quantize_pallas(
        x, block=block, interpret=(mode == "pallas_interpret")
    )


def int8_dequantize(q, scales, *, n: int, block: int = 256, impl: Optional[str] = None):
    mode = resolve_impl(impl)
    if mode == "ref":
        return ref.int8_dequantize_ref(q, scales, n, block=block)
    return int8_dequantize_pallas(
        q, scales, n=n, block=block, interpret=(mode == "pallas_interpret")
    )
