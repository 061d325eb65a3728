"""Paged decode attention — Pallas TPU kernel.

One query token per row against that row's own pages of the block-granular
KV pool, read where the pool keeps them.  The gather path
(``repro.models.attention.paged_gather_attention``, taken without
``use_pallas``) first copies every row's table out of the pool into a
contiguous ``(B, T, K, hd)`` array at the widest row's width and then
attends over it under a mask; this kernel reads only the pages a row
holds, up to its own ``pos``, and writes nothing but the output.

- grid = (rows,), sequential: each step is one row, whose pages are walked in
  blocks of ``pages_per_block``.  The block after the current one (the next
  row's first block at a row's end) is fetched while the current one is
  computed: two VMEM buffers for K and two for V.
- The pools stay in HBM (``pl.ANY``) in the layout the engine keeps:
  ``(reps, pages + 1, page_size, K, hd)``, whole and layer-stacked, the
  leading axes merged by a free reshape.  A page is one DMA of all K heads;
  the layer's pages start at ``layer * (pages + 1)``.  The layer, the
  per-row key counts and the tables are scalar-prefetched.
- Heads are read out of a block two at a time: a bf16 block viewed as
  uint32 holds heads ``2j`` and ``2j+1`` of one key in the low and high
  halves of one word, and a strided load picks head pair ``j`` of every key.
- Online softmax in float32 over each KV head's ``G`` query heads; scores
  scaled by ``1/sqrt(hd)``, keys past ``pos`` masked, probabilities cast to
  the compute dtype before ``p·V``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def supported(dtype, num_kv_heads: int, head_dim: int) -> bool:
    """Pools this kernel reads: bf16, KV heads in pairs, lane-wide heads."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and num_kv_heads % 2 == 0
            and head_dim % 128 == 0)


def _kernel(layer_ref, lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_scr, l_scr, acc_scr, slot_ref, *,
            width: int, pool_pages: int, sm_scale: float):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    _, npb, ps, K, hd = kbuf.shape
    bk = npb * ps
    base = layer_ref[0] * pool_pages

    def copies(r, i, slot):
        """Block ``i`` of row ``r``: (page held?, copy of K, copy of V) for
        each page of the block."""
        held = (lens_ref[r] + ps - 1) // ps
        out = []
        for j in range(npb):
            col = i * npb + j
            pid = base + tables_ref[r * width + jnp.minimum(col, width - 1)]
            out.append((col < held,
                        pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[slot, j],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[slot, j],
                                              sems.at[1, slot])))
        return out

    def start(r, i, slot):
        for held, ck, cv in copies(r, i, slot):
            @pl.when(held)
            def _():
                ck.start()
                cv.start()

    def wait(r, i, slot):
        for held, ck, cv in copies(r, i, slot):
            @pl.when(held)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    n = lens_ref[b]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def head_pair(buf, j):
        """KV heads ``2j`` and ``2j+1`` of a (npb, ps, K, hd) bf16 block,
        (bk, hd) each."""
        words = buf.reshape(bk * K, hd).bitcast(jnp.uint32)
        w = words[pl.ds(j, bk, stride=K // 2), :]
        lo = pltpu.bitcast(w << 16, jnp.float32)
        hi = pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
        return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)

    def body(i, slot):
        last = i + 1 == (n + bk - 1) // bk
        nr = jnp.where(last, b + 1, b)

        @pl.when(nr < rows)
        def _():
            start(nr, jnp.where(last, 0, i + 1), 1 - slot)

        wait(b, i, slot)
        col = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = col < n
        # slots past the row's pages hold stale VMEM: zero their values
        valid_v = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < n
        for j in range(K // 2):
            pairs = zip(head_pair(kbuf.at[slot], j),
                        head_pair(vbuf.at[slot], j))
            for kh, (k, v) in enumerate(pairs, start=2 * j):
                q = q_ref[0, kh]                               # (G, hd)
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = jnp.where(valid, s * sm_scale, NEG_INF)    # (G, bk)
                m_prev = m_scr[kh]                             # (G, 1)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_scr[kh] = alpha * l_scr[kh] + jnp.sum(p, axis=1,
                                                        keepdims=True)
                v = jnp.where(valid_v, v, 0)
                acc_scr[kh] = alpha * acc_scr[kh] + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[kh] = m_new
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, (n + bk - 1) // bk, body, slot_ref[0])
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode(q, k_pool, v_pool, lengths, tables, layer, *,
                 pages_per_block: int = 16, interpret: bool):
    """q: (B, H, hd); k_pool, v_pool: (reps, pages + 1, page_size, K, hd) or
    (pages + 1, page_size, K, hd); lengths: (B,) keys each row attends over
    (its ``pos + 1``, at least 1); tables: (B, W) page ids of each row;
    layer: () index into ``reps``.  Returns (B, H, hd) in q's dtype."""
    B, H, hd = q.shape
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    reps, pool_pages, ps, K, _ = k_pool.shape
    G = H // K
    W = tables.shape[1]
    npb = min(pages_per_block, W)
    k_flat = k_pool.reshape(reps * pool_pages, ps, K, hd)
    v_flat = v_pool.reshape(reps * pool_pages, ps, K, hd)
    row = lambda b, *_: (b, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, width=W, pool_pages=pool_pages,
                          sm_scale=1.0 / np.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, K, G, hd), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, K, G, hd), row),
            scratch_shapes=[
                pltpu.VMEM((2, npb, ps, K, hd), k_pool.dtype),
                pltpu.VMEM((2, npb, ps, K, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((K, G, 1), jnp.float32),     # m: running max
                pltpu.VMEM((K, G, 1), jnp.float32),     # l: denominator
                pltpu.VMEM((K, G, hd), jnp.float32),    # acc: unnormalised o
                pltpu.SMEM((1,), jnp.int32),            # next block's buffer
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), q.reshape(B, K, G, hd),
      k_flat, v_flat)
    return out.reshape(B, H, hd)
