"""Jitted wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

import functools

from repro.kernels.autodiff import kernel_with_ref_vjp
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.rglru.rglru_scan import rglru_scan


@functools.lru_cache(maxsize=16)
def _diff_op(chunk, block_w, interpret):
    return kernel_with_ref_vjp(
        functools.partial(rglru_scan, chunk=chunk, block_w=block_w,
                          interpret=interpret),
        rglru_ref)


def linear_recurrence(a, b, *, chunk: int = 64, block_w: int = 128,
                      interpret: bool):
    """Differentiable: Pallas kernel forward, oracle backward."""
    return _diff_op(chunk, block_w, interpret)(a, b)


def linear_recurrence_ref(a, b):
    return rglru_ref(a, b)
