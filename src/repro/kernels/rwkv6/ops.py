"""Jitted wrapper: model layout (B,S,H,hd) ↔ kernel layout (B,H,S,hd)."""
from __future__ import annotations

import functools

from repro.kernels.autodiff import kernel_with_ref_vjp
from repro.kernels.rwkv6.ref import rwkv6_ref
from repro.kernels.rwkv6.rwkv6_scan import rwkv6_scan


@functools.lru_cache(maxsize=16)
def _diff_op(chunk, interpret):
    return kernel_with_ref_vjp(
        functools.partial(rwkv6_scan, chunk=chunk, interpret=interpret),
        rwkv6_ref)


def time_mix_scan(r, k, v, lw, u, *, chunk: int = 32, interpret: bool):
    """Model-layout entry point. r,k,v,lw: (B,S,H,hd); u: (H,hd).

    Differentiable: Pallas kernel forward, oracle-recompute backward."""
    tr = lambda t: t.transpose(0, 2, 1, 3)
    y = _diff_op(chunk, interpret)(tr(r), tr(k), tr(v), tr(lw), u)
    return y.transpose(0, 2, 1, 3)


def time_mix_ref(r, k, v, lw, u):
    tr = lambda t: t.transpose(0, 2, 1, 3)
    return rwkv6_ref(tr(r), tr(k), tr(v), tr(lw), u).transpose(0, 2, 1, 3)
