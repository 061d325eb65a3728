"""RWKV-6 chunked recurrence — Pallas TPU kernel.

TPU adaptation of the flash-linear-attention chunked algorithm:

- grid = (batch, heads, chunks); the chunk dimension is innermost and
  sequential, so the per-head matrix state S ∈ R^{hd×hd} (fp32) lives in
  VMEM scratch across chunk iterations — the cross-chunk recurrence costs
  zero HBM traffic,
- within a chunk the pairwise decay ``exp(cum_{t-1} − cum_j)`` (always ≤ 0
  in the exponent → no overflow) is formed one output row at a time, as a
  (C, hd) tile in a ``fori_loop`` over the chunk's C timesteps,
- the cross-chunk read and the state update are MXU matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, state_scr,
                 r_scr, cum_scr, y_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)        # log-decay ≤ 0
    u = u_ref[0].astype(jnp.float32)             # (1, hd)
    S = state_scr[...]                           # (hd, hd)
    hd = S.shape[0]

    C = chunk
    # every matmul here is f32 at HIGHEST: at the TPU's default precision
    # the kernel drifts ~2e-3 from the f32 reference
    hi = jax.lax.Precision.HIGHEST
    # inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum)
    tril = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(jnp.float32)
    cum = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                              precision=hi, preferred_element_type=jnp.float32)
    cum_scr[...] = cum - lw                      # exclusive: cum_{t-1}
    r_scr[...] = r        # f32 rows: a dynamic one-row load needs 32-bit
    col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)

    def row(t, carry):
        # y_t = sum_{j<t} (sum_h r_th k_jh exp(cum_{t-1,h} - cum_jh)) v_j,
        # one output row per step; the exponent is ≤ 0 wherever j < t, so
        # the pairwise decay never overflows
        rows = pl.ds(t, 1)
        r_t = r_scr[rows, :]                                      # (1, hd)
        expn = jnp.minimum(cum_scr[rows, :] - cum, 0.0)          # (C, hd)
        a = jnp.sum(r_t * k * jnp.exp(expn), axis=1, keepdims=True)
        a = jnp.where(col < t, a, 0.0)                            # (C, 1)
        y_scr[rows, :] = jnp.sum(a * v, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, C, row, 0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)             # (C, 1)
    y = y_scr[...] + diag * v
    # cross-chunk read: r_t decayed back to chunk start
    y = y + jax.lax.dot_general(r * jnp.exp(cum_scr[...]), S,
                                (((1,), (0,)), ((), ())), precision=hi,
                                preferred_element_type=jnp.float32)
    # state update
    last = cum[C - 1:C]                                          # (1, hd)
    dec_k = jnp.exp(last - cum)                                  # (C, hd) ≤ 1
    # S rows scaled by exp(last): diag(exp(last)) @ S, built without a
    # row-to-column relayout
    eye_hd = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0) ==
              jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))
    decay = jnp.where(eye_hd, jnp.exp(last), 0.0)
    S_new = jax.lax.dot_general(
        decay, S, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        (k * dec_k), v, (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    state_scr[...] = S_new
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, lw, u, *, chunk: int = 32, interpret: bool):
    """r,k,v: (B, H, S, hd); lw: (B, H, S, hd) fp32 log-decay; u: (H, hd).

    Returns y: (B, H, S, hd).  S must be divisible by ``chunk``.
    """
    B, H, S, hd = r.shape
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    grid = (B, H, n)
    spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    # u as (H, 1, hd): a (1, hd) block then spans the array's last two dims,
    # which the TPU tiling rule accepts; a (1, hd) block of (H, hd) is not
    u3 = u.reshape(H, 1, hd)
    return pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),     # state S
                        pltpu.VMEM((chunk, hd), jnp.float32),  # r
                        pltpu.VMEM((chunk, hd), jnp.float32),  # cum_{t-1}
                        pltpu.VMEM((chunk, hd), jnp.float32)], # intra y
        interpret=interpret,
    )(r, k, v, lw, u3)
