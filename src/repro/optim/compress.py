"""int8 error-feedback gradient compression for cross-pod all-reduce.

At 2+ pods the data-parallel gradient reduction crosses the pod boundary
(DCN, ~10x slower than ICI) — the dominant collective term in the multi-pod
roofline. ``compressed_psum`` implements an int8 reduce-scatter/all-gather
pair inside ``shard_map``:

  1. pad + split the flat gradient into one chunk per device on the axis,
  2. blockwise-int8 quantize every chunk (Pallas codec on TPU),
  3. ``all_to_all`` the int8 chunks + f32 scales  (wire: 1 byte/elem),
  4. locally dequantize + sum -> this device's reduced chunk,
  5. re-quantize, ``all_gather`` (wire: 1 byte/elem), dequantize.

Wire traffic is ~4x smaller than an f32 ring all-reduce (2 bytes/elem total
vs 8). Quantization residuals are fed back into the next step's gradient
(error feedback), which keeps SGD/AdamW convergence unbiased — tested in
tests/test_compression.py against uncompressed training.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops

BLOCK = 256


def _quant_chunks(x2d, impl):
    """x2d: (n_dev, chunk) -> (q int8 (n_dev, chunk), scales (n_dev, nb))."""
    n_dev, chunk = x2d.shape
    q, s = ops.int8_quantize(x2d.reshape(-1), block=BLOCK, impl=impl)
    nb = chunk // BLOCK
    return q.reshape(n_dev, chunk), s.reshape(n_dev, nb)


def compressed_psum(x: jnp.ndarray, axis_name: str, *, impl: Optional[str] = "ref"):
    """Sum `x` (any shape) across `axis_name` with int8 wire format.

    Must run inside shard_map/pmap with `axis_name` bound. Returns the full
    (summed) array, same shape/dtype as x.
    """
    n_dev = jax.lax.axis_size(axis_name)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    chunk = -(-n // (n_dev * BLOCK)) * BLOCK  # per-device chunk, BLOCK-aligned
    flat = jnp.pad(flat, (0, chunk * n_dev - n))
    x2d = flat.reshape(n_dev, chunk)

    q, s = _quant_chunks(x2d, impl)
    # reduce-scatter: device i receives chunk i from everyone (int8 + scales)
    q_rs = jax.lax.all_to_all(q[:, None], axis_name, split_axis=0, concat_axis=1)
    s_rs = jax.lax.all_to_all(s[:, None], axis_name, split_axis=0, concat_axis=1)
    # q_rs: (1, n_dev, chunk) -> dequantize each sender's chunk and sum
    deq = q_rs[0].astype(jnp.float32).reshape(n_dev, chunk // BLOCK, BLOCK) * s_rs[
        0
    ][..., None]
    local_sum = deq.sum(axis=0).reshape(chunk)

    # all-gather the reduced chunks in int8
    q2, s2 = ops.int8_quantize(local_sum, block=BLOCK, impl=impl)
    qg = jax.lax.all_gather(q2, axis_name)  # (n_dev, chunk)
    sg = jax.lax.all_gather(s2, axis_name)
    out = (
        qg.astype(jnp.float32).reshape(n_dev, chunk // BLOCK, BLOCK) * sg[..., None]
    ).reshape(-1)[:n]
    return out.reshape(orig_shape).astype(orig_dtype)


def compressed_grad_tree(grads, residuals, axis_name: str, *, impl="ref"):
    """Error-feedback compressed reduction over a gradient pytree.

    g_eff = g + residual;   wire = Q(g_eff);   new_residual = g_eff - Q(g_eff)
    (residual is measured against the LOCAL quantization — the reduction of
    quantized values is exact, so local residual capture suffices.)
    Returns (reduced_grads, new_residuals).
    """
    n_dev = jax.lax.axis_size(axis_name)

    def one(g, r):
        g_eff = g.astype(jnp.float32) + r
        flat = g_eff.reshape(-1)
        n = flat.shape[0]
        pad = (-n) % BLOCK
        q, s = ops.int8_quantize(flat, block=BLOCK, impl=impl)
        deq = ops.int8_dequantize(q, s, n=n, block=BLOCK, impl=impl)
        new_r = (flat - deq).reshape(g.shape)
        reduced = compressed_psum(deq.reshape(g.shape), axis_name, impl=impl)
        return (reduced / n_dev).astype(g.dtype), new_r

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (
        treedef.unflatten([o[0] for o in out]),
        treedef.unflatten([o[1] for o in out]),
    )


def init_residuals(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
