"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

Two execution paths:

- **train/prefill** — *decompressed*: up-project the latent to per-head
  K_nope/V, run standard chunked GQA-style attention over
  head_dim = qk_nope + qk_rope.
- **decode** — *absorbed*: the cache stores only the latent ``c_kv``
  (B, T, kv_lora=512) plus the shared rope key (B, T, 64); W_uk is absorbed
  into the query and W_uv into the output so no per-head K/V are ever
  materialized. This is the paper's 93% KV-cache reduction and the reason
  the decode_32k cell is memory-cheap despite 128 heads.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm
from repro.models.attention import chunked_attention, NEG_INF


def mla_specs(cfg) -> dict:
    a = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_dq": cm.ParamSpec((d, a.q_lora_rank), ("embed", "lora"), dt),
        "q_norm": cm.ParamSpec((a.q_lora_rank,), ("lora",), jnp.float32, "zeros"),
        "w_uq": cm.ParamSpec((a.q_lora_rank, h, a.qk_nope_head_dim + a.qk_rope_head_dim),
                             ("lora", "heads", None), dt),
        "w_dkv": cm.ParamSpec((d, a.kv_lora_rank + a.qk_rope_head_dim),
                              ("embed", None), dt),
        "kv_norm": cm.ParamSpec((a.kv_lora_rank,), (None,), jnp.float32, "zeros"),
        "w_uk": cm.ParamSpec((a.kv_lora_rank, h, a.qk_nope_head_dim),
                             ("lora", "heads", None), dt),
        "w_uv": cm.ParamSpec((a.kv_lora_rank, h, a.v_head_dim),
                             ("lora", "heads", None), dt),
        "wo": cm.ParamSpec((h, a.v_head_dim, d), ("heads", None, "embed"), dt),
    }


def _latent(cfg, p, x, positions):
    """Down-project to (c_kv, k_rope); rope applied to the shared rope key."""
    a = cfg.mla
    dkv = jnp.einsum("btd,dr->btr", x, p["w_dkv"])
    c_kv = cm.rmsnorm(dkv[..., :a.kv_lora_rank], p["kv_norm"])
    k_rope = dkv[..., a.kv_lora_rank:]                            # (B,T,rope_dim)
    k_rope = cm.rope(k_rope, positions, cfg.rope_theta)
    return c_kv, k_rope


def _queries(cfg, p, x, positions):
    a = cfg.mla
    q = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
    q = cm.rmsnorm(q, p["q_norm"])
    from repro.distributed.ctx import constrain_qkv

    q = constrain_qkv(jnp.einsum("bsr,rhk->bshk", q, p["w_uq"]))
    q_nope, q_rope = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    q_rope = cm.rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention(cfg, p: dict, x, positions):
    """Train-path MLA (decompressed)."""
    from repro.distributed.sp_block import sp_mla_block

    blk = sp_mla_block(cfg, p, x, positions, with_cache=False)
    if blk is not None:
        return blk[0]
    a = cfg.mla
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latent(cfg, p, x, positions)
    from repro.distributed.ctx import constrain_qkv

    k_nope = constrain_qkv(jnp.einsum("btr,rhk->bthk", c_kv, p["w_uk"]))
    v = constrain_qkv(jnp.einsum("btr,rhk->bthk", c_kv, p["w_uv"]))
    B, T = x.shape[0], x.shape[1]
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, T, cfg.num_heads, a.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    # v_head_dim may differ from qk head_dim — pad V so chunked_attention's
    # uniform head_dim holds, slice after
    from repro.distributed.sp_attention import (maybe_sp_attention,
                                                 maybe_sp_attention_fused)

    qk_hd, v_hd = q.shape[-1], v.shape[-1]
    if v_hd < qk_hd:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_hd - v_hd)))
    y = maybe_sp_attention_fused(q, k, v, p["wo"], causal=True,
                                 chunk=cfg.attn_chunk, v_head=a.v_head_dim)
    if y is not None:
        return y
    o = maybe_sp_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    from repro.distributed.ctx import constrain_residual

    o = o[..., :a.v_head_dim]
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"]).astype(x.dtype)
    return constrain_residual(y)


def mla_prefill(cfg, p: dict, x, positions, *, past: Optional[dict] = None,
                past_len: int = 0):
    """With ``past`` (latents of an already-cached prefix), only the suffix
    is computed on the decompressed path: suffix queries at absolute
    ``positions`` attend over concat(past, suffix) latents, and the
    returned cache covers the suffix only."""
    from repro.distributed.sp_block import sp_mla_block

    if past is not None:
        a = cfg.mla
        c_suf, kr_suf = _latent(cfg, p, x, positions)
        c_all = jnp.concatenate([past["c_kv"].astype(c_suf.dtype), c_suf],
                                axis=1)
        kr_all = jnp.concatenate([past["k_rope"].astype(kr_suf.dtype), kr_suf],
                                 axis=1)
        q_nope, q_rope = _queries(cfg, p, x, positions)
        k_nope = jnp.einsum("btr,rhk->bthk", c_all, p["w_uk"])
        v = jnp.einsum("btr,rhk->bthk", c_all, p["w_uv"])
        B, T = c_all.shape[0], c_all.shape[1]
        k_rope_h = jnp.broadcast_to(kr_all[:, :, None, :],
                                    (B, T, cfg.num_heads, a.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        qk_hd, v_hd = q.shape[-1], v.shape[-1]
        if v_hd < qk_hd:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_hd - v_hd)))
        o = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                              q_offset=past_len)
        o = o[..., :a.v_head_dim]
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"]).astype(x.dtype)
        return out, {"c_kv": c_suf, "k_rope": kr_suf}

    blk = sp_mla_block(cfg, p, x, positions, with_cache=True)
    if blk is not None:
        return blk
    out = mla_attention(cfg, p, x, positions)
    c_kv, k_rope = _latent(cfg, p, x, positions)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def _absorbed_read(cfg, p: dict, x_dtype, q_nope, q_rope, c_kv, k_rope, valid):
    """Absorbed-path scores + latent readout shared by the contiguous and
    paged decode variants.  valid: bool mask broadcastable to (B,1,H,T)."""
    a = cfg.mla
    # absorb W_uk into q: (B,1,H,nope) x (r,H,nope) -> (B,1,H,r)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    scores = jnp.einsum("bshr,btr->bsht", q_lat, c_kv).astype(jnp.float32)
    scores = scores + jnp.einsum("bshk,btk->bsht", q_rope,
                                 k_rope).astype(jnp.float32)
    scores = scores / np.sqrt(a.qk_nope_head_dim + a.qk_rope_head_dim)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x_dtype)
    o_lat = jnp.einsum("bsht,btr->bshr", probs, c_kv)             # latent readout
    o = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"])            # absorb W_uv
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]).astype(x_dtype)


def mla_decode(cfg, p: dict, x, cache: dict, pos):
    """Absorbed decode: scores/read run directly in the 512-d latent space.

    ``pos`` is a scalar or a (B,) vector of per-row absolute positions
    (continuous batching).
    """
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    posv = pos[:, None] if per_row else jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope = _queries(cfg, p, x, posv)                    # (B,1,H,·)
    c_new, kr_new = _latent(cfg, p, x, posv)
    if per_row:
        b = jnp.arange(x.shape[0])
        c_kv = cache["c_kv"].at[b, pos].set(c_new[:, 0].astype(cache["c_kv"].dtype))
        k_rope = cache["k_rope"].at[b, pos].set(kr_new[:, 0].astype(cache["k_rope"].dtype))
    else:
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, axis=1)

    T = c_kv.shape[1]
    idx = jnp.arange(T, dtype=jnp.int32)
    valid = (idx[None, :] <= pos[:, None]) if per_row else (idx <= pos)
    valid = valid[:, None, None, :] if per_row else valid[None, None, None, :]
    out = _absorbed_read(cfg, p, x.dtype, q_nope, q_rope, c_kv, k_rope, valid)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_paged_decode(cfg, p: dict, x, cache: dict, pos, tables, *,
                     page_size: int, layer=None):
    """Absorbed decode against a block-granular paged latent pool.

    cache c_kv: (num_pages+1, page_size, kv_lora); k_rope likewise — row 0
    is the null page.  With ``layer``, both are the whole layer-stacked
    pool and only layer ``layer`` is written and read, as in
    ``paged_decode_attention``.  tables: (B, max_pages) int32 page ids (0
    where unallocated); pos: (B,) per-row absolute positions.  Same engine
    guarantees as ``paged_decode_attention``: valid positions are backed
    by real pages and the write page is private to its row.
    """
    a = cfg.mla
    pos = jnp.asarray(pos, jnp.int32)
    posv = pos[:, None]
    q_nope, q_rope = _queries(cfg, p, x, posv)                    # (B,1,H,·)
    c_new, kr_new = _latent(cfg, p, x, posv)
    B = x.shape[0]
    b = jnp.arange(B)
    pid = tables[b, pos // jnp.int32(page_size)]
    off = pos % jnp.int32(page_size)
    at = () if layer is None else (layer,)
    c_pool = cache["c_kv"].at[at + (pid, off)].set(
        c_new[:, 0].astype(cache["c_kv"].dtype))
    kr_pool = cache["k_rope"].at[at + (pid, off)].set(
        kr_new[:, 0].astype(cache["k_rope"].dtype))
    T = tables.shape[1] * page_size
    c_kv = c_pool[at + (tables,)].reshape(B, T, a.kv_lora_rank)
    k_rope = kr_pool[at + (tables,)].reshape(B, T, a.qk_rope_head_dim)
    idx = jnp.arange(T, dtype=jnp.int32)
    valid = (idx[None, :] <= pos[:, None])[:, None, None, :]
    out = _absorbed_read(cfg, p, x.dtype, q_nope, q_rope, c_kv, k_rope, valid)
    return out, {"c_kv": c_pool, "k_rope": kr_pool}
