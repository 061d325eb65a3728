"""Pallas TPU kernels (flash attention, paged decode attention, RG-LRU scan,
RWKV-6 scan).

Each kernel takes ``interpret`` with no default.  The model path asks
:func:`interpret_mode` — the one place that decides it — so a kernel runs
compiled on a TPU and in the Pallas interpreter anywhere else.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True off the TPU: there the Pallas interpreter runs the kernel body."""
    return jax.default_backend() != "tpu"
