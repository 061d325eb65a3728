"""Grouped-query attention with chunked (query-blocked) softmax.

The chunked path is the memory-critical design decision of the whole model
substrate (DESIGN.md §5.1): scores are only ever materialized for one query
block at a time — ``(B, chunk, H, T)`` instead of ``(B, S, H, T)`` — which is
what lets the 32k-prefill cells fit the 16 GB/chip HBM budget. The same
function is the pure-jnp oracle for the Pallas flash-attention kernel
(``repro.kernels.flash_attention``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm

NEG_INF = -1e30


def attn_specs(cfg, *, bias: Optional[bool] = None, cross: bool = False) -> dict:
    """Param specs for one (cross-)attention layer."""
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if cross:
        k = h  # cross-attn layers use full MHA over image/encoder tokens
    dt = jnp.dtype(cfg.param_dtype)
    use_bias = cfg.qkv_bias if bias is None else bias
    # NOTE (EXPERIMENTS.md §Perf H1d, refuted): sharding hd when heads don't
    # divide converts the grad all-reduce into a reduce-scatter but costs
    # MORE in weight all-gathers under remat (qwen: collective 17.5->19.9s);
    # heads replicate instead and the matcher's roofline twin sees the cost.
    s = {
        "wq": cm.ParamSpec((d, h, hd), ("embed", "heads", None), dt),
        "wk": cm.ParamSpec((d, k, hd), ("embed", "kv_heads", None), dt),
        "wv": cm.ParamSpec((d, k, hd), ("embed", "kv_heads", None), dt),
        "wo": cm.ParamSpec((h, hd, d), ("heads", None, "embed"), dt),
    }
    if use_bias:
        s["bq"] = cm.ParamSpec((h, hd), ("heads", None), jnp.float32, "zeros")
        s["bk"] = cm.ParamSpec((k, hd), ("kv_heads", None), jnp.float32, "zeros")
        s["bv"] = cm.ParamSpec((k, hd), ("kv_heads", None), jnp.float32, "zeros")
    return s


def project_qkv(p: dict, x, xkv=None, sp_constrain: bool = False):
    """(B,S,d) -> q (B,S,H,hd), k/v (B,T,K,hd)."""
    from repro.distributed.ctx import constrain_qkv

    xkv = x if xkv is None else xkv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dgk->btgk", xkv, p["wk"])
    v = jnp.einsum("btd,dgk->btgk", xkv, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if sp_constrain:
        q = constrain_qkv(q)
        k = constrain_qkv(k)
        v = constrain_qkv(v)
    return q, k, v


def out_proj(p: dict, o):
    from repro.distributed.ctx import constrain_residual

    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"]).astype(o.dtype)
    return constrain_residual(y)


def _block_attend(q_blk, k, v, row_pos, col_pos, *, causal, window, kv_valid):
    """Attention for one query block against the full key range.

    q_blk: (B, C, K, G, hd) fp-compute; k/v: (B, T, K, hd);
    row_pos: (C,) / (B, C) and col_pos: (T,) / (B, T) absolute positions
    (2-D when each batch row sits on its own timeline — continuous batching);
    kv_valid: (T,) / (B, T) bool or None.  Returns (B, C, K, G, hd).
    """
    hd = q_blk.shape[-1]
    scores = jnp.einsum("bckgh,btkh->bckgt", q_blk, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    row = row_pos if row_pos.ndim == 2 else row_pos[None]          # (Bm, C)
    col = col_pos if col_pos.ndim == 2 else col_pos[None]          # (Bm, T)
    mask = jnp.ones((max(row.shape[0], col.shape[0]),
                     row.shape[1], col.shape[1]), jnp.bool_)       # (Bm, C, T)
    if causal:
        mask &= col[:, None, :] <= row[:, :, None]
    if window is not None:
        mask &= col[:, None, :] > (row[:, :, None] - window)
    if kv_valid is not None:
        kvv = kv_valid if kv_valid.ndim == 2 else kv_valid[None]
        mask &= kvv[:, None, :]
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
    return jnp.einsum("bckgt,btkh->bckgh", probs, v)


def pallas_attention(cfg, q, k, v, *, causal: bool):
    """Route through the Pallas flash kernel (compiled on a TPU, the
    interpreter elsewhere — see :func:`repro.kernels.interpret_mode`).

    Only sound for from-scratch causal/bidirectional attention without
    windows/offsets — callers gate on that.
    """
    from repro.kernels import interpret_mode
    from repro.kernels.flash_attention.ops import mha

    return mha(q, k, v, causal=causal, interpret=interpret_mode())


def chunked_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                      chunk: int = 1024, q_offset: int = 0,
                      kv_valid=None, cfg=None):
    """GQA attention, scanning over query blocks of size ``chunk``.

    q: (B, S, H, hd); k, v: (B, T, K, hd) with H = K*G.
    ``q_offset`` places the query block inside the KV timeline (prefill with a
    pre-existing cache / decode).  Exact — no approximation; block size only
    bounds the live score buffer.

    When ``cfg.use_pallas`` is set and the call is kernel-compatible, the
    Pallas flash kernel takes over (kernels are a selectable first-class
    layer, not a fork of the model).
    """
    if (cfg is not None and cfg.use_pallas and window is None
            and q_offset == 0 and kv_valid is None
            and q.shape[1] == k.shape[1]):
        return pallas_attention(cfg, q, k, v, causal=causal)
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    col_pos = jnp.arange(T, dtype=jnp.int32)

    if S <= chunk:
        row_pos = q_offset + jnp.arange(S, dtype=jnp.int32)
        o = _block_attend(qg, k, v, row_pos, col_pos, causal=causal,
                          window=window, kv_valid=kv_valid)
        return o.reshape(B, S, H, hd)

    pad = (-S) % chunk
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nb = (S + pad) // chunk
    qb = qg.reshape(B, nb, chunk, K, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, blk):
        i, qi = blk
        row_pos = q_offset + i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        oi = _block_attend(qi, k, v, row_pos, col_pos, causal=causal,
                           window=window, kv_valid=kv_valid)
        return None, oi

    # flash-style recompute: without this, scan saves every block's softmax
    # for backward — i.e. the full (B,S,H,T) attention matrix
    body = jax.checkpoint(body)
    _, ob = jax.lax.scan(body, None, (jnp.arange(nb, dtype=jnp.int32), qb))
    o = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * chunk, K, G, hd)
    if pad:
        o = o[:, :S]
    return o.reshape(B, S, H, hd)


def self_attention(cfg, p: dict, x, positions, *, causal=True,
                   window: Optional[int] = None):
    """Full-sequence self-attention (train / encoder)."""
    from repro.distributed.sp_attention import maybe_sp_attention_fused
    from repro.distributed.sp_block import sp_gqa_block

    blk = sp_gqa_block(cfg, p, x, positions, causal=causal, window=window,
                       with_cache=False)
    if blk is not None:
        return blk[0]
    q, k, v = project_qkv(p, x, sp_constrain=True)
    if cfg.family != "encdec":  # whisper uses absolute pos-emb, not RoPE
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
    y = maybe_sp_attention_fused(q, k, v, p["wo"], causal=causal,
                                 window=window, chunk=cfg.attn_chunk)
    if y is not None:
        return y
    o = chunked_attention(q, k, v, causal=causal, window=window,
                          chunk=cfg.attn_chunk, cfg=cfg)
    return out_proj(p, o)


def prefill_attention(cfg, p: dict, x, positions, *, window: Optional[int] = None,
                      past: Optional[dict] = None, past_len: int = 0):
    """Self-attention that also returns the KV cache (ring-buffered if local).

    With ``past`` (k/v of an already-cached prefix, (B, past_len, K, hd)),
    only the suffix is computed: queries at ``positions`` (absolute, i.e.
    ``past_len + arange(S)``) attend over concat(past, suffix) and the
    returned cache covers the *suffix only* — the prefix's pages already
    hold its K/V.
    """
    from repro.distributed.sp_attention import maybe_sp_attention_fused
    from repro.distributed.sp_block import sp_gqa_block

    if past is not None:
        q, k, v = project_qkv(p, x, sp_constrain=True)
        if cfg.family != "encdec":
            q = cm.rope(q, positions, cfg.rope_theta)
            k = cm.rope(k, positions, cfg.rope_theta)
        k_all = jnp.concatenate([past["k"].astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([past["v"].astype(v.dtype), v], axis=1)
        o = chunked_attention(q, k_all, v_all, causal=True, window=window,
                              chunk=cfg.attn_chunk, q_offset=past_len)
        return out_proj(p, o), {"k": k, "v": v}

    blk = sp_gqa_block(cfg, p, x, positions, causal=True, window=window,
                       with_cache=True)
    if blk is not None:
        y, cache = blk
        if window is not None and cache["k"].shape[1] > window:
            cache = {"k": cache["k"][:, -window:], "v": cache["v"][:, -window:]}
        return y, cache
    q, k, v = project_qkv(p, x, sp_constrain=True)
    if cfg.family != "encdec":
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
    y = maybe_sp_attention_fused(q, k, v, p["wo"], causal=True,
                                 window=window, chunk=cfg.attn_chunk)
    if y is None:
        o = chunked_attention(q, k, v, causal=True, window=window,
                              chunk=cfg.attn_chunk, cfg=cfg)
        y = out_proj(p, o)
    if window is not None and k.shape[1] > window:
        k, v = k[:, -window:], v[:, -window:]
    return y, {"k": k, "v": v}


def decode_attention(cfg, p: dict, x, cache: dict, pos, *,
                     window: Optional[int] = None):
    """One-token decode against a (B, T, K, hd) cache.

    Global attention: cache holds T = max_seq slots, slot ``pos`` is written.
    Local attention: cache is a ring buffer of ``window`` slots.
    ``pos`` is a scalar (the whole batch at one absolute position) or a
    (B,) vector (continuous batching: each row on its own timeline).
    """
    q, k_new, v_new = project_qkv(p, x)           # (B, 1, ., .)
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    posv = pos[:, None] if per_row else jnp.full((1,), pos, jnp.int32)
    if cfg.family != "encdec":
        q = cm.rope(q, posv, cfg.rope_theta)
        k_new = cm.rope(k_new, posv, cfg.rope_theta)
    k_cache, v_cache = cache["k"], cache["v"]
    T = k_cache.shape[1]
    slot = pos % jnp.int32(T) if window is not None else pos
    if per_row:
        b = jnp.arange(q.shape[0])
        k_cache = k_cache.at[b, slot].set(k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[b, slot].set(v_new[:, 0].astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    idx = jnp.arange(T, dtype=jnp.int32)
    if window is None:
        col_pos = idx
        kv_valid = (idx[None, :] <= pos[:, None]) if per_row else (idx <= pos)
    else:
        # ring buffer: slot i holds absolute position p with p % T == i, the
        # largest such p <= pos
        prow = pos[:, None] if per_row else pos
        col_pos = prow - ((prow - idx) % jnp.int32(T))    # (B, T) or (T,)
        kv_valid = col_pos >= 0
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    qg = q.reshape(B, 1, K, H // K, hd)
    o = _block_attend(qg, k_cache, v_cache, posv, col_pos, causal=True,
                      window=window, kv_valid=kv_valid)
    o = o.reshape(B, 1, H, hd)
    return out_proj(p, o), {"k": k_cache, "v": v_cache}


def paged_decode_attention(cfg, p: dict, x, cache: dict, pos, tables, *,
                           page_size: int, layer=None):
    """One-token decode against a block-granular paged KV pool.

    cache k/v: (num_pages+1, page_size, K, hd) — row 0 is the null page
    that dead batch rows write into and no one reads.  With ``layer``, k/v
    are the whole layer-stacked pool (reps, num_pages+1, page_size, K, hd)
    and only layer ``layer`` is written and read: one scatter on the stack,
    so a scan carrying it updates it in place.
    tables: (B, max_pages) int32 page ids (0 where unallocated) — the
    per-row page-index vectors generalizing the per-row position vectors.
    pos: (B,) per-row absolute positions.  The engine guarantees every
    position <= pos[b] is backed by a real page in row b's table, and that
    the write page (block ``pos // page_size``) is private to row b —
    shared prefix pages are immutable by construction.

    With ``cfg.use_pallas`` and a pool the kernel reads, the Pallas paged
    decode kernel attends, reading each row's own pages in place; otherwise
    :func:`paged_gather_attention`, its reference.
    """
    q, k_new, v_new = project_qkv(p, x)           # (B, 1, ., .)
    pos = jnp.asarray(pos, jnp.int32)
    posv = pos[:, None]
    if cfg.family != "encdec":
        q = cm.rope(q, posv, cfg.rope_theta)
        k_new = cm.rope(k_new, posv, cfg.rope_theta)
    k_pool, v_pool = cache["k"], cache["v"]
    B = q.shape[0]
    b = jnp.arange(B)
    pid = tables[b, pos // jnp.int32(page_size)]  # (B,) write page per row
    off = pos % jnp.int32(page_size)
    at = () if layer is None else (layer,)
    k_pool = k_pool.at[at + (pid, off)].set(k_new[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[at + (pid, off)].set(v_new[:, 0].astype(v_pool.dtype))
    from repro.kernels import interpret_mode, paged_decode as kernel

    K, hd = k_pool.shape[-2], k_pool.shape[-1]
    if cfg.use_pallas and kernel.supported(k_pool.dtype, K, hd):
        o = kernel.paged_decode(q[:, 0], k_pool, v_pool, pos + 1, tables,
                                0 if layer is None else layer,
                                interpret=interpret_mode())[:, None]
    else:
        o = paged_gather_attention(q, k_pool, v_pool, pos, tables,
                                   page_size=page_size, layer=layer)
    return out_proj(p, o), {"k": k_pool, "v": v_pool}


def paged_gather_attention(q, k_pool, v_pool, pos, tables, *, page_size: int,
                           layer=None):
    """q: (B, 1, H, hd) at positions ``pos`` against the pool through the
    tables: every row's table gathered into a contiguous (B, T, K, hd) copy
    at the table's width, then attended under a mask."""
    B, _, H, _ = q.shape
    at = () if layer is None else (layer,)
    K, hd = k_pool.shape[-2], k_pool.shape[-1]
    T = tables.shape[1] * page_size
    k = k_pool[at + (tables,)].reshape(B, T, K, hd)   # gather through the table
    v = v_pool[at + (tables,)].reshape(B, T, K, hd)
    idx = jnp.arange(T, dtype=jnp.int32)
    kv_valid = idx[None, :] <= pos[:, None]
    qg = q.reshape(B, 1, K, H // K, hd)
    o = _block_attend(qg, k, v, pos[:, None], idx, causal=True, window=None,
                      kv_valid=kv_valid)
    return o.reshape(B, 1, H, hd)


def cross_attention(cfg, p: dict, x, kv_cache: dict):
    """Cross-attention against precomputed encoder/image K,V (full MHA)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    o = chunked_attention(q, kv_cache["k"], kv_cache["v"], causal=False,
                          chunk=cfg.attn_chunk)
    return out_proj(p, o)


def cross_kv(p: dict, ctx):
    """Precompute cross-attention K,V from encoder/image embeddings."""
    k = jnp.einsum("btd,dgk->btgk", ctx, p["wk"])
    v = jnp.einsum("btd,dgk->btgk", ctx, p["wv"])
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return {"k": k, "v": v}
