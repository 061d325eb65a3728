"""Jitted public wrapper: model-layout (B,S,H,hd) → kernel layout and back.

``use_pallas`` on an ArchConfig routes ``repro.models.attention`` through
this op; the pure-JAX chunked path is the default and the test reference.
"""
from __future__ import annotations

import jax

import functools

from repro.kernels.autodiff import kernel_with_ref_vjp
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@functools.lru_cache(maxsize=32)
def _diff_op(causal, block_q, block_k, interpret):
    return kernel_with_ref_vjp(
        functools.partial(flash_attention, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret),
        functools.partial(attention_ref, causal=causal))


def mha(q, k, v, *, causal: bool = True, block_q: int = 128,
        block_k: int = 128, interpret: bool):
    """q: (B, S, H, hd); k, v: (B, T, K, hd). Returns (B, S, H, hd).

    Differentiable: Pallas kernel forward, oracle-recompute backward."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _diff_op(causal, block_q, block_k, interpret)(qt, kt, vt)
    return o.transpose(0, 2, 1, 3)


def mha_ref(q, k, v, *, causal: bool = True):
    o = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal)
    return o.transpose(0, 2, 1, 3)
