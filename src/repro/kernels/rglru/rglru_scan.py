"""RG-LRU diagonal linear recurrence — Pallas TPU kernel.

h_t = a_t ⊙ h_{t-1} + b_t over the time axis, channel-blocked:

- grid = (batch, width_blocks, chunks); chunks innermost/sequential with the
  carried state h ∈ R^{wb} (fp32) in VMEM scratch,
- the channel dimension is blocked to the 128-lane VPU width (this is a
  VPU kernel, not an MXU one — elementwise FMA over lanes),
- within a chunk the recurrence is a ``fori_loop`` over C timesteps that
  loads and stores one row of the refs per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, state_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def step(t, h):
        # one timestep row, read from and written to the refs: indexing a
        # loaded value by the loop counter does not lower on TPU
        row = (0, pl.ds(t, 1), slice(None))
        h = (a_ref[row].astype(jnp.float32) * h
             + b_ref[row].astype(jnp.float32))
        h_ref[row] = h.astype(h_ref.dtype)
        return h

    state_scr[...] = jax.lax.fori_loop(0, chunk, step, state_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_scan(a, b, *, chunk: int = 64, block_w: int = 128,
               interpret: bool):
    """a, b: (B, S, W) — returns h: (B, S, W) with h_t = a_t·h_{t-1} + b_t."""
    B, S, W = a.shape
    assert S % chunk == 0, (S, chunk)
    wb = min(block_w, W)
    assert W % wb == 0, (W, wb)
    grid = (B, W // wb, S // chunk)
    spec = pl.BlockSpec((1, chunk, wb), lambda bi, wi, ci: (bi, ci, wi))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, wb), jnp.float32)],
        interpret=interpret,
    )(a, b)
