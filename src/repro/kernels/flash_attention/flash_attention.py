"""Blocked causal GQA flash attention — Pallas TPU kernel.

TPU adaptation (not a CUDA port): the kernel is shaped around the MXU and
the sequential-innermost-grid-dimension property of TPU Pallas —

- grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
  innermost and therefore *sequential per core*, so the online-softmax
  running state (m, l, acc) lives in VMEM scratch that persists across kv
  iterations (no atomics / shared-memory reductions as on GPU),
- q/k/v blocks are staged HBM→VMEM by BlockSpec index maps; the GQA
  mapping (kv head = q head // group) happens in the index map, so grouped
  heads share kv traffic,
- block shapes default to (128, head_dim) — MXU-aligned (multiples of 8
  sublanes × 128 lanes for f32).

Causality is exploited at block granularity: kv blocks strictly above the
diagonal are skipped via ``pl.when`` (no compute, no VMEM writes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_k: int, causal: bool,
                  seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level causal skip: kv block strictly above the diagonal
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (bq, bk)
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
        mask = cols < seq_len
        if causal:
            mask &= cols <= rows
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q: (B, H, S, hd); k, v: (B, K, T, hd) with H = K·G. Returns (B,H,S,hd).

    ``interpret=True`` executes the kernel body off the TPU (validation);
    callers on the model path pass ``repro.kernels.interpret_mode()``.
    """
    B, H, S, hd = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    sm_scale = 1.0 / np.sqrt(hd)

    bq = min(block_q, S)
    bk = min(block_k, T)
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (S + pad_q) // bq
    nk = (T + pad_k) // bk

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, block_q=bq,
                          block_k=bk, causal=causal, seq_len=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=G: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=G: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S + pad_q, hd), q.dtype),
        scratch_shapes=[
            _vmem((bq,), jnp.float32),       # m: running row max
            _vmem((bq,), jnp.float32),       # l: running denominator
            _vmem((bq, hd), jnp.float32),    # acc: unnormalized output
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :S]
    return out


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
