"""Config → model: parameter specs, train loss, prefill/decode steps.

Public surface used by the launcher, dry-run, tests and benchmarks:

- :func:`model_specs`        — ParamSpec pytree for an arch
- :func:`loss_fn`            — full train loss (chunked cross-entropy + MoE aux)
- :func:`build_prefill_step` / :func:`build_decode_step`
- :func:`count_params`       — analytic N (and active-N for MoE)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm
from repro.models.transformer import (_PAGED_MIXER_LEAVES, LayerDef, Stack,
                                      build_layer_defs)
from repro.distributed.ctx import constrain


def _decoder(cfg) -> Stack:
    return Stack(cfg)


def _encoder(cfg) -> Stack:
    defs = [LayerDef("attn", "dense")] * cfg.encoder_layers
    return Stack(cfg, bidirectional=True, defs=defs)


def model_specs(cfg) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    s = {
        "embed": cm.ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                              dt, "small"),
        "decoder": _decoder(cfg).specs(),
        "final_norm": cm.norm_spec(cfg, cfg.d_model),
    }
    if cfg.family == "rwkv":
        # RWKV normalises the embedding before the first block
        s["ln0"] = cm.norm_spec(cfg, cfg.d_model)
    if not cfg.tie_embeddings:
        s["unembed"] = cm.ParamSpec((cfg.d_model, cfg.vocab_size),
                                    ("embed", "vocab"), dt)
    if cfg.family == "encdec":
        s["encoder"] = _encoder(cfg).specs()
        s["enc_norm"] = cm.norm_spec(cfg, cfg.d_model)
    return s


def count_params(cfg, active_only: bool = False, include_embed: bool = True) -> int:
    total = 0
    m = cfg.moe
    for spec in cm.tree_specs(model_specs(cfg)):
        n = int(np.prod(spec.shape))
        if not include_embed and "vocab" in spec.axes:
            continue
        if active_only and m is not None and "expert" in spec.axes:
            n = int(n * m.top_k / m.num_experts)
        total += n
    return total


def _sinusoid(positions, d_model: int):
    """Whisper-style sinusoidal position embedding; positions: (S,) or scalar."""
    pos = jnp.atleast_1d(positions).astype(jnp.float32)
    half = d_model // 2
    freq = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                   / max(half - 1, 1))
    ang = pos[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens]
    if cfg.family == "rwkv":
        x = cm.apply_norm(cfg, params["ln0"], x)
    return constrain(x.astype(jnp.dtype(cfg.compute_dtype)),
                     ("batch", "act_seq", None))


def _logit_kernel(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def chunked_xent(cfg, features, kernel, labels, mask=None):
    """Cross-entropy without materializing (B,S,V) logits.

    features: (B,S,d); kernel: (d,V); labels: (B,S) int32.
    Scans over sequence chunks of cfg.xent_chunk.
    """
    B, S, d = features.shape
    C = cfg.xent_chunk if S % cfg.xent_chunk == 0 else S
    n = S // C
    f = features.reshape(B, n, C, d).transpose(1, 0, 2, 3)
    l = labels.reshape(B, n, C).transpose(1, 0, 2)
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mk = mask.reshape(B, n, C).transpose(1, 0, 2)

    def body(acc, blk):
        fb, lb, mb = blk
        logits = jnp.einsum("bcd,dv->bcv", fb, kernel).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        loss = jnp.sum((lse - gold) * mb)
        return (acc[0] + loss, acc[1] + jnp.sum(mb)), None

    # recompute logits in backward — never materialize (B,S,V)
    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)), (f, l, mk))
    return tot / jnp.maximum(cnt, 1.0)


AUX_WEIGHT = 0.01


def loss_fn(cfg, params, batch):
    """batch: {tokens, labels[, frames][, image_embeds]} → (loss, metrics)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = _embed_tokens(cfg, params, tokens)
    ctx = None
    if cfg.family == "encdec":
        enc_x = batch["frames"].astype(x.dtype)
        enc_pos = jnp.arange(enc_x.shape[1], dtype=jnp.int32)
        enc_x = enc_x + _sinusoid(enc_pos, cfg.d_model).astype(x.dtype)
        ctx, _ = _encoder(cfg).train(params["encoder"], enc_x, enc_pos)
        ctx = cm.apply_norm(cfg, params["enc_norm"], ctx)
        x = x + _sinusoid(positions, cfg.d_model).astype(x.dtype)
    elif cfg.family == "vision":
        ctx = batch["image_embeds"].astype(x.dtype)
    feats, aux = _decoder(cfg).train(params["decoder"], x, positions, ctx)
    feats = cm.apply_norm(cfg, params["final_norm"], feats)
    xent = chunked_xent(cfg, feats, _logit_kernel(cfg, params), batch["labels"])
    loss = xent + AUX_WEIGHT * aux
    return loss, {"xent": xent, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving


def build_prefill_step(cfg):
    dec = _decoder(cfg)

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        S = tokens.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        x = _embed_tokens(cfg, params, tokens)
        ctx = None
        if cfg.family == "encdec":
            enc_x = batch["frames"].astype(x.dtype)
            enc_pos = jnp.arange(enc_x.shape[1], dtype=jnp.int32)
            enc_x = enc_x + _sinusoid(enc_pos, cfg.d_model).astype(x.dtype)
            ctx, _ = _encoder(cfg).train(params["encoder"], enc_x, enc_pos)
            ctx = cm.apply_norm(cfg, params["enc_norm"], ctx)
            x = x + _sinusoid(positions, cfg.d_model).astype(x.dtype)
        elif cfg.family == "vision":
            ctx = batch["image_embeds"].astype(x.dtype)
        feats, cache, _ = dec.prefill(params["decoder"], x, positions, ctx)
        feats = cm.apply_norm(cfg, params["final_norm"], feats[:, -1:])
        logits = jnp.einsum("bsd,dv->bsv", feats,
                            _logit_kernel(cfg, params)).astype(jnp.float32)
        return cache, logits[:, 0]

    return prefill_step


def build_decode_step(cfg):
    dec = _decoder(cfg)

    def decode_step(params, cache, token, pos):
        """token: (B,1) int32; pos: () or (B,) int32 — absolute position(s)
        of `token` (a (B,) vector puts each row on its own timeline)."""
        x = _embed_tokens(cfg, params, token)
        if cfg.family == "encdec":
            pe = _sinusoid(pos, cfg.d_model).astype(x.dtype)
            x = x + (pe[:, None] if jnp.ndim(pos) == 1 else pe[None])
        feats, cache, _ = dec.decode(params["decoder"], x, cache, pos)
        feats = cm.apply_norm(cfg, params["final_norm"], feats)
        logits = jnp.einsum("bsd,dv->bsv", feats,
                            _logit_kernel(cfg, params)).astype(jnp.float32)
        return cache, logits[:, 0]

    return decode_step


def decode_cache(cfg, batch: int, seq_len: int, abstract: bool = False):
    return _decoder(cfg).cache(batch, seq_len, abstract)


# ---------------------------------------------------------------------------
# paged serving (block-granular KV pool + prefix reuse)


def decode_cache_paged(cfg, batch: int, seq_len: int, pool_pages: int,
                       page_size: int, abstract: bool = False):
    """Decode cache with attn/mla leaves in ``(pool_pages+1, page_size, ...)``
    pool layout (row 0 = null page); resident leaves stay ``(batch, ...)``."""
    return _decoder(cfg).paged_cache(batch, seq_len, pool_pages, page_size,
                                     abstract)


def paged_cache_flags(cfg):
    """Cache-structured bool tree marking pool-layout leaves."""
    return _decoder(cfg).paged_flags()


def paged_support(cfg):
    """-> (any_paged, prefix_ok): whether the arch has pageable cache
    leaves at all, and whether prefix-cache reuse is sound for it (every
    mixer pageable, no cross-attention, no encoder/image context)."""
    defs = build_layer_defs(cfg)
    any_paged = any(d.mixer in _PAGED_MIXER_LEAVES for d in defs)
    prefix_ok = (cfg.family not in ("encdec", "vision")
                 and all(d.mixer in _PAGED_MIXER_LEAVES and not d.cross
                         for d in defs))
    return any_paged, prefix_ok


def _past_seq_len(past) -> int:
    """Static prefix length from a past tree's leaf shapes (trace-time)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(past)[0]:
        name = None
        for p in reversed(path):
            if isinstance(p, jax.tree_util.DictKey):
                name = p.key
                break
        if name in ("k", "v"):
            return int(leaf.shape[-3])
        if name in ("c_kv", "k_rope"):
            return int(leaf.shape[-2])
    raise ValueError("past tree has no recognizable KV leaf")


def build_prefill_past_step(cfg):
    """Suffix-only prefill against an already-cached prefix.

    ``past`` is a cache-structured tree of the prefix's K/V (latents for
    MLA) at batch 1; its static leaf shapes carry the prefix length, so the
    jit specializes per (suffix_len, prefix_len) pair.  Only archs where
    :func:`paged_support` reports ``prefix_ok`` may use this.
    """
    dec = _decoder(cfg)

    def prefill_past_step(params, batch, past):
        tokens = batch["tokens"]
        S = tokens.shape[1]
        past_len = _past_seq_len(past)
        positions = past_len + jnp.arange(S, dtype=jnp.int32)
        x = _embed_tokens(cfg, params, tokens)
        feats, cache, _ = dec.prefill(params["decoder"], x, positions, None,
                                      past=past, past_len=past_len)
        feats = cm.apply_norm(cfg, params["final_norm"], feats[:, -1:])
        logits = jnp.einsum("bsd,dv->bsv", feats,
                            _logit_kernel(cfg, params)).astype(jnp.float32)
        return cache, logits[:, 0]

    return prefill_past_step


def build_decode_step_paged(cfg, page_size: int):
    dec = _decoder(cfg)

    def decode_step(params, cache, token, pos, tables):
        """token: (B,1) int32; pos: (B,) absolute positions; tables:
        (B, max_pages) int32 page ids (0 = unallocated/null)."""
        x = _embed_tokens(cfg, params, token)
        if cfg.family == "encdec":
            pe = _sinusoid(pos, cfg.d_model).astype(x.dtype)
            x = x + (pe[:, None] if jnp.ndim(pos) == 1 else pe[None])
        feats, cache, _ = dec.decode(params["decoder"], x, cache, pos,
                                     tables=tables, page_size=page_size)
        feats = cm.apply_norm(cfg, params["final_norm"], feats)
        logits = jnp.einsum("bsd,dv->bsv", feats,
                            _logit_kernel(cfg, params)).astype(jnp.float32)
        return cache, logits[:, 0]

    return decode_step
