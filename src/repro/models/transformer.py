"""Generic decoder/encoder stack over heterogeneous layer kinds.

The stack is described by a list of :class:`LayerDef` (mixer kind × FFN kind ×
optional cross-attention), which is factored into

    prefix layers  +  (cycle of length c) × reps  +  suffix layers

so that the repeated cycle runs under a single ``jax.lax.scan`` with stacked
parameters — HLO size and compile time stay flat in depth (96-layer nemotron
compiles like a 1-layer model). Prefix covers e.g. the dense first layer of
the MoE archs; suffix covers pattern remainders (recurrentgemma's 38 = 12×3+2).

Three modes share the same layer application:
- ``train``   — full sequence, no cache,
- ``prefill`` — full sequence, emits the decode cache,
- ``decode``  — one token against the cache.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn as ffn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.distributed.ctx import constrain, constrain_cache


@dataclasses.dataclass(frozen=True)
class LayerDef:
    mixer: str              # attn | local_attn | recurrent | rwkv | mla | cross_only
    ffn: str                # dense | moe | rwkv_cm
    cross: bool = False     # additional cross-attn (whisper decoder)


def build_layer_defs(cfg) -> List[LayerDef]:
    if cfg.family == "rwkv":
        return [LayerDef("rwkv", "rwkv_cm")] * cfg.num_layers
    if cfg.family == "vision":
        e = cfg.cross_attn_every
        return [LayerDef("cross_only" if (i % e) == e - 1 else "attn", "dense")
                for i in range(cfg.num_layers)]
    if cfg.family == "encdec":
        return [LayerDef("attn", "dense", cross=True)] * cfg.num_layers
    if cfg.moe is not None:
        mixer = "mla" if cfg.mla is not None else "attn"
        f = cfg.moe.first_moe_layer
        return [LayerDef(mixer, "dense" if i < f else "moe")
                for i in range(cfg.num_layers)]
    kinds = cfg.layer_kinds()
    return [LayerDef(k, "dense") for k in kinds]


def factor_layers(cfg, defs: List[LayerDef]) -> Tuple[List, List, int, List]:
    """-> (prefix_defs, cycle_defs, reps, suffix_defs)."""
    prefix_len = 0
    if cfg.moe is not None:
        prefix_len = cfg.moe.first_moe_layer
    cyc_len = 1
    if cfg.family == "hybrid":
        cyc_len = len(cfg.block_pattern)
    elif cfg.family == "vision":
        cyc_len = cfg.cross_attn_every
    body = defs[prefix_len:]
    reps = len(body) // cyc_len
    cycle = body[:cyc_len] if reps else []
    suffix = body[reps * cyc_len:]
    for i, d in enumerate(body[: reps * cyc_len]):
        assert d == cycle[i % cyc_len], f"non-cyclic layer structure at {i}"
    return defs[:prefix_len], cycle, reps, suffix


# ---------------------------------------------------------------------------
# per-layer specs


def layer_specs(cfg, ld: LayerDef) -> dict:
    s = {"ln1": cm.norm_spec(cfg, cfg.d_model)}
    if ld.mixer in ("attn", "local_attn"):
        s["mixer"] = attn.attn_specs(cfg)
    elif ld.mixer == "mla":
        s["mixer"] = mla_mod.mla_specs(cfg)
    elif ld.mixer == "recurrent":
        s["mixer"] = rglru_mod.rglru_specs(cfg)
    elif ld.mixer == "rwkv":
        s["mixer"] = rwkv_mod.rwkv_specs(cfg)
    elif ld.mixer == "cross_only":
        s["mixer"] = attn.attn_specs(cfg, cross=True)
        s["xgate"] = cm.ParamSpec((1,), (None,), jnp.float32, "zeros")
    if ld.cross:
        s["ln_cross"] = cm.norm_spec(cfg, cfg.d_model)
        s["cross"] = attn.attn_specs(cfg, cross=True)
    s["ln2"] = cm.norm_spec(cfg, cfg.d_model)
    if ld.ffn == "dense":
        s["ffn"] = ffn_mod.ffn_specs(cfg)
    elif ld.ffn == "moe":
        s["ffn"] = moe_mod.moe_specs(cfg)
    elif ld.ffn == "rwkv_cm":
        s["ffn"] = ffn_mod.rwkv_channel_mix_specs(cfg)
    return s


def stack_specs(tree, n: int):
    return jax.tree.map(
        lambda s: cm.ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype,
                               s.init, s.scale),
        tree, is_leaf=cm.is_spec)


# ---------------------------------------------------------------------------
# caches


def layer_cache(cfg, ld: LayerDef, batch: int, seq_len: int, abstract: bool):
    """Decode-cache template for one layer (None if the layer is stateless)."""
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    pdt = jnp.dtype(cfg.param_dtype)

    def mk(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    c = {}
    if ld.mixer == "attn":
        c = {"k": mk((batch, seq_len, K, hd), pdt), "v": mk((batch, seq_len, K, hd), pdt)}
    elif ld.mixer == "local_attn":
        w = min(cfg.local_window, seq_len)
        c = {"k": mk((batch, w, K, hd), pdt), "v": mk((batch, w, K, hd), pdt)}
    elif ld.mixer == "mla":
        a = cfg.mla
        c = {"c_kv": mk((batch, seq_len, a.kv_lora_rank), pdt),
             "k_rope": mk((batch, seq_len, a.qk_rope_head_dim), pdt)}
    elif ld.mixer == "recurrent":
        r = cfg.recurrent
        c = {"h": mk((batch, r.lru_width), jnp.float32),
             "conv": mk((batch, r.conv_width - 1, r.lru_width), jnp.float32)}
    elif ld.mixer == "rwkv":
        c = {"s": mk((batch, cfg.num_heads, cfg.rwkv.head_dim, cfg.rwkv.head_dim),
                     jnp.float32),
             "ts_tm": mk((batch, cfg.d_model), pdt),
             "ts_cm": mk((batch, cfg.d_model), pdt)}
    elif ld.mixer == "cross_only":
        t = cfg.num_image_tokens
        c = {"ck": mk((batch, t, cfg.num_heads, hd), pdt),
             "cv": mk((batch, t, cfg.num_heads, hd), pdt)}
    if ld.cross:
        t = cfg.encoder_frames
        # cross-attention layers are full MHA (attn_specs(cross=True))
        c["cross_k"] = mk((batch, t, cfg.num_heads, hd), pdt)
        c["cross_v"] = mk((batch, t, cfg.num_heads, hd), pdt)
    return c


def stack_cache(tree, n: int, abstract: bool):
    def f(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((n,) + x.shape, x.dtype)
        return jnp.broadcast_to(x, (n,) + x.shape)
    return jax.tree.map(f, tree)


#: cache leaves that page (global, unbounded-growth KV); every other leaf is
#: *resident* — bounded per-row state (ring-buffer window, recurrent/rwkv
#: carries, precomputed cross K/V) that stays slot-granular
_PAGED_MIXER_LEAVES = {"attn": ("k", "v"), "mla": ("c_kv", "k_rope")}


def layer_cache_paged(cfg, ld: LayerDef, batch: int, seq_len: int,
                      pool_pages: int, page_size: int, abstract: bool):
    """Like :func:`layer_cache`, but pageable leaves take the pool layout
    ``(pool_pages + 1, page_size, ...)`` — row 0 is the null/trash page —
    shared across batch rows via per-row page tables.  Resident leaves keep
    their slot-granular ``(batch, ...)`` layout."""
    c = layer_cache(cfg, ld, batch, seq_len, abstract)
    pdt = jnp.dtype(cfg.param_dtype)

    def mk(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    if ld.mixer == "attn":
        K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        c["k"] = mk((pool_pages + 1, page_size, K, hd), pdt)
        c["v"] = mk((pool_pages + 1, page_size, K, hd), pdt)
    elif ld.mixer == "mla":
        a = cfg.mla
        c["c_kv"] = mk((pool_pages + 1, page_size, a.kv_lora_rank), pdt)
        c["k_rope"] = mk((pool_pages + 1, page_size, a.qk_rope_head_dim), pdt)
    return c


def layer_paged_flags(cfg, ld: LayerDef) -> dict:
    """Cache-structured tree of bools: True on pageable leaves."""
    paged = _PAGED_MIXER_LEAVES.get(ld.mixer, ())
    base = layer_cache(cfg, ld, 1, 2, abstract=True)
    return {name: name in paged for name in base}


# ---------------------------------------------------------------------------
# layer application


def _mixer_train(cfg, ld, p, x, positions, ctx, states):
    """Full-seq mixer. states: dict with optional rwkv/recurrent carries."""
    h = cm.apply_norm(cfg, p["ln1"], x)
    new_state = None
    if ld.mixer == "attn":
        causal = not states.get("bidirectional", False)
        out = attn.self_attention(cfg, p["mixer"], h, positions, causal=causal)
    elif ld.mixer == "local_attn":
        out = attn.self_attention(cfg, p["mixer"], h, positions,
                                  window=cfg.local_window)
    elif ld.mixer == "mla":
        out = mla_mod.mla_attention(cfg, p["mixer"], h, positions)
    elif ld.mixer == "recurrent":
        out, new_state = rglru_mod.rglru_block(cfg, p["mixer"], h)
    elif ld.mixer == "rwkv":
        out, s, last = rwkv_mod.rwkv_time_mix(cfg, p["mixer"], h,
                                              want_state=False)
        new_state = (s, last)
    elif ld.mixer == "cross_only":
        out = attn.cross_attention(cfg, p["mixer"], h,
                                   attn.cross_kv(p["mixer"], ctx))
        out = out * jnp.tanh(p["xgate"]).astype(out.dtype)
    x = x + out
    if ld.cross:
        hc = cm.apply_norm(cfg, p["ln_cross"], x)
        x = x + attn.cross_attention(cfg, p["cross"], hc,
                                     attn.cross_kv(p["cross"], ctx))
    return x, new_state


def _ffn_apply(cfg, ld, p, x, aux, ts_prev=None):
    h = cm.apply_norm(cfg, p["ln2"], x)
    if ld.ffn == "moe":
        out, a = moe_mod.moe_ffn(cfg, p["ffn"], h)
        aux = aux + a
    elif ld.ffn == "rwkv_cm":
        if ts_prev is None:
            prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        else:
            prev = jnp.concatenate([ts_prev[:, None], h[:, :-1]], axis=1)
        out = ffn_mod.rwkv_channel_mix(cfg, p["ffn"], h, prev)
    else:
        out = ffn_mod.ffn(cfg, p["ffn"], h)
    return x + out, aux


def apply_layer_train(cfg, ld, p, x, positions, ctx, aux, bidirectional=False):
    x = constrain(x, ("batch", "act_seq", None))
    x, _ = _mixer_train(cfg, ld, p, x, positions, ctx,
                        {"bidirectional": bidirectional})
    x, aux = _ffn_apply(cfg, ld, p, x, aux)
    return x, aux


def apply_layer_prefill(cfg, ld, p, x, positions, ctx, aux,
                        past=None, past_len=0):
    """Train-path compute + emit decode cache.

    ``past`` (prefix-cache reuse) carries this layer's already-computed
    prefix K/V (or latents); only attn/mla mixers support it — the engine
    gates prefix sharing to stacks made purely of those."""
    x = constrain(x, ("batch", "act_seq", None))
    cache = {}
    h = cm.apply_norm(cfg, p["ln1"], x)
    if past is not None and ld.mixer not in _PAGED_MIXER_LEAVES:
        raise ValueError(f"prefix reuse unsupported for mixer {ld.mixer!r}")
    if ld.mixer == "attn":
        out, kv = attn.prefill_attention(cfg, p["mixer"], h, positions,
                                         past=past, past_len=past_len)
        # right-pad the cache to the cell's full seq_len is done by caller
        cache.update(kv)
    elif ld.mixer == "local_attn":
        out, kv = attn.prefill_attention(cfg, p["mixer"], h, positions,
                                         window=cfg.local_window)
        cache.update(kv)
    elif ld.mixer == "mla":
        out, kv = mla_mod.mla_prefill(cfg, p["mixer"], h, positions,
                                      past=past, past_len=past_len)
        cache.update(kv)
    elif ld.mixer == "recurrent":
        out, (hf, conv) = rglru_mod.rglru_block(cfg, p["mixer"], h)
        cache.update({"h": hf, "conv": conv})
    elif ld.mixer == "rwkv":
        out, s, last = rwkv_mod.rwkv_time_mix(cfg, p["mixer"], h)
        cache.update({"s": s, "ts_tm": last})
    elif ld.mixer == "cross_only":
        ckv = attn.cross_kv(p["mixer"], ctx)
        out = attn.cross_attention(cfg, p["mixer"], h, ckv)
        out = out * jnp.tanh(p["xgate"]).astype(out.dtype)
        cache.update({"ck": ckv["k"], "cv": ckv["v"]})
    x = x + out
    if ld.cross:
        hc = cm.apply_norm(cfg, p["ln_cross"], x)
        ckv = attn.cross_kv(p["cross"], ctx)
        x = x + attn.cross_attention(cfg, p["cross"], hc, ckv)
        cache.update({"cross_k": ckv["k"], "cross_v": ckv["v"]})
    h2 = cm.apply_norm(cfg, p["ln2"], x)
    if ld.ffn == "rwkv_cm":
        prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        x = x + ffn_mod.rwkv_channel_mix(cfg, p["ffn"], h2, prev)
        cache["ts_cm"] = h2[:, -1]
    else:
        x, aux = _ffn_apply(cfg, ld, p, x, aux)
    return x, constrain_cache(cache), aux


def apply_layer_decode(cfg, ld, p, x, cache, pos, aux,
                       tables=None, page_size=None, layer=None):
    """x: (B,1,d). Returns (x, new_cache).

    With ``tables`` (paged serving), attn/mla leaves live in a shared page
    pool gathered through per-row page tables; resident mixers are
    untouched — they keep per-row state and the per-row ``pos`` vector.
    With ``layer``, the pool leaves are the whole layer-stacked pool and
    this layer's rows of it are updated in place."""
    x = constrain(x, ("batch", "act_seq", None))
    h = cm.apply_norm(cfg, p["ln1"], x)
    new_cache = dict(cache)
    if ld.mixer == "attn":
        if tables is not None:
            out, kv = attn.paged_decode_attention(
                cfg, p["mixer"], h, {"k": cache["k"], "v": cache["v"]}, pos,
                tables, page_size=page_size, layer=layer)
        else:
            out, kv = attn.decode_attention(
                cfg, p["mixer"], h, {"k": cache["k"], "v": cache["v"]}, pos)
        new_cache.update(kv)
    elif ld.mixer == "local_attn":
        out, kv = attn.decode_attention(cfg, p["mixer"], h,
                                        {"k": cache["k"], "v": cache["v"]}, pos,
                                        window=cfg.local_window)
        new_cache.update(kv)
    elif ld.mixer == "mla":
        if tables is not None:
            out, kv = mla_mod.mla_paged_decode(
                cfg, p["mixer"], h,
                {"c_kv": cache["c_kv"], "k_rope": cache["k_rope"]}, pos,
                tables, page_size=page_size, layer=layer)
        else:
            out, kv = mla_mod.mla_decode(cfg, p["mixer"], h,
                                         {"c_kv": cache["c_kv"],
                                          "k_rope": cache["k_rope"]}, pos)
        new_cache.update(kv)
    elif ld.mixer == "recurrent":
        out, hf, conv = rglru_mod.rglru_decode(cfg, p["mixer"], h,
                                               cache["h"], cache["conv"])
        new_cache.update({"h": hf, "conv": conv})
    elif ld.mixer == "rwkv":
        out, s, last = rwkv_mod.rwkv_decode(cfg, p["mixer"], h, cache["s"],
                                            cache["ts_tm"])
        new_cache.update({"s": s, "ts_tm": last})
    elif ld.mixer == "cross_only":
        out = attn.cross_attention(cfg, p["mixer"], h,
                                   {"k": cache["ck"], "v": cache["cv"]})
        out = out * jnp.tanh(p["xgate"]).astype(out.dtype)
    x = x + out
    if ld.cross:
        hc = cm.apply_norm(cfg, p["ln_cross"], x)
        x = x + attn.cross_attention(cfg, p["cross"], hc,
                                     {"k": cache["cross_k"], "v": cache["cross_v"]})
    h2 = cm.apply_norm(cfg, p["ln2"], x)
    if ld.ffn == "rwkv_cm":
        prev = cache["ts_cm"][:, None]
        x = x + ffn_mod.rwkv_channel_mix(cfg, p["ffn"], h2, prev)
        new_cache["ts_cm"] = h2[:, 0]
    elif ld.ffn == "moe":
        out, a = moe_mod.moe_ffn(cfg, p["ffn"], h2)
        x = x + out
        aux = aux + a
    else:
        x = x + ffn_mod.ffn(cfg, p["ffn"], h2)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stack


class Stack:
    """Factored layer stack bound to a config (decoder by default)."""

    def __init__(self, cfg, bidirectional: bool = False,
                 defs: Optional[List[LayerDef]] = None):
        self.cfg = cfg
        self.bidirectional = bidirectional
        self.defs = defs if defs is not None else build_layer_defs(cfg)
        self.prefix, self.cycle, self.reps, self.suffix = factor_layers(cfg, self.defs)

    # -- specs --------------------------------------------------------------
    def specs(self) -> dict:
        s = {}
        if self.prefix:
            s["prefix"] = {str(i): layer_specs(self.cfg, d)
                           for i, d in enumerate(self.prefix)}
        if self.reps:
            s["blocks"] = {str(i): stack_specs(layer_specs(self.cfg, d), self.reps)
                           for i, d in enumerate(self.cycle)}
        if self.suffix:
            s["suffix"] = {str(i): layer_specs(self.cfg, d)
                           for i, d in enumerate(self.suffix)}
        return s

    def cache(self, batch: int, seq_len: int, abstract: bool = False) -> dict:
        c = {}
        if self.prefix:
            c["prefix"] = {str(i): layer_cache(self.cfg, d, batch, seq_len, abstract)
                           for i, d in enumerate(self.prefix)}
        if self.reps:
            c["blocks"] = {str(i): stack_cache(
                layer_cache(self.cfg, d, batch, seq_len, abstract), self.reps, abstract)
                for i, d in enumerate(self.cycle)}
        if self.suffix:
            c["suffix"] = {str(i): layer_cache(self.cfg, d, batch, seq_len, abstract)
                           for i, d in enumerate(self.suffix)}
        return c

    def paged_cache(self, batch: int, seq_len: int, pool_pages: int,
                    page_size: int, abstract: bool = False) -> dict:
        """Decode cache with pageable leaves in pool layout (null page at
        row 0); ``seq_len`` still sizes the resident leaves."""
        def lc(d):
            return layer_cache_paged(self.cfg, d, batch, seq_len,
                                     pool_pages, page_size, abstract)
        c = {}
        if self.prefix:
            c["prefix"] = {str(i): lc(d) for i, d in enumerate(self.prefix)}
        if self.reps:
            c["blocks"] = {str(i): stack_cache(lc(d), self.reps, abstract)
                           for i, d in enumerate(self.cycle)}
        if self.suffix:
            c["suffix"] = {str(i): lc(d) for i, d in enumerate(self.suffix)}
        return c

    def paged_flags(self) -> dict:
        """Cache-structured bool tree: True on pageable (pool-layout) leaves.
        Matches :meth:`cache`'s tree structure exactly (bools under
        ``blocks`` are not layer-stacked — a leaf's pagedness is uniform
        across the scanned cycle repetitions)."""
        c = {}
        if self.prefix:
            c["prefix"] = {str(i): layer_paged_flags(self.cfg, d)
                           for i, d in enumerate(self.prefix)}
        if self.reps:
            c["blocks"] = {str(i): layer_paged_flags(self.cfg, d)
                           for i, d in enumerate(self.cycle)}
        if self.suffix:
            c["suffix"] = {str(i): layer_paged_flags(self.cfg, d)
                           for i, d in enumerate(self.suffix)}
        return c

    # -- forward ------------------------------------------------------------
    def train(self, p: dict, x, positions, ctx=None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        for i, d in enumerate(self.prefix):
            x, aux = apply_layer_train(cfg, d, p["prefix"][str(i)], x, positions,
                                       ctx, aux, self.bidirectional)
        if self.reps:
            def body(carry, bp):
                x, aux = carry
                for i, d in enumerate(self.cycle):
                    x, aux = apply_layer_train(cfg, d, bp[str(i)], x, positions,
                                               ctx, aux, self.bidirectional)
                return (x, aux), None
            body = cm.maybe_remat(body, cfg.remat_policy)
            (x, aux), _ = jax.lax.scan(body, (x, aux), p["blocks"])
        for i, d in enumerate(self.suffix):
            x, aux = apply_layer_train(cfg, d, p["suffix"][str(i)], x, positions,
                                       ctx, aux, self.bidirectional)
        return x, aux

    def prefill(self, p: dict, x, positions, ctx=None, past=None, past_len=0):
        """``past`` (prefix-cache reuse): a cache-structured tree of this
        stack's prefix K/V at length ``past_len``; only the suffix in ``x``
        is computed and the emitted cache covers that suffix."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        if self.prefix:
            caches["prefix"] = {}
            for i, d in enumerate(self.prefix):
                x, c, aux = apply_layer_prefill(
                    cfg, d, p["prefix"][str(i)], x, positions, ctx, aux,
                    past=None if past is None else past["prefix"][str(i)],
                    past_len=past_len)
                caches["prefix"][str(i)] = c
        if self.reps:
            def body(carry, scanned):
                x, aux = carry
                bp, bpast = scanned if past is not None else (scanned, None)
                cs = {}
                for i, d in enumerate(self.cycle):
                    x, c, aux = apply_layer_prefill(
                        cfg, d, bp[str(i)], x, positions, ctx, aux,
                        past=None if bpast is None else bpast[str(i)],
                        past_len=past_len)
                    cs[str(i)] = c
                return (x, aux), cs
            body = cm.maybe_remat(body, cfg.remat_policy)
            scanned = (p["blocks"] if past is None
                       else (p["blocks"], past["blocks"]))
            (x, aux), caches["blocks"] = jax.lax.scan(body, (x, aux), scanned)
        if self.suffix:
            caches["suffix"] = {}
            for i, d in enumerate(self.suffix):
                x, c, aux = apply_layer_prefill(
                    cfg, d, p["suffix"][str(i)], x, positions, ctx, aux,
                    past=None if past is None else past["suffix"][str(i)],
                    past_len=past_len)
                caches["suffix"][str(i)] = c
        return x, caches, aux

    def decode(self, p: dict, x, caches: dict, pos, tables=None,
               page_size=None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new = {}
        if self.prefix:
            new["prefix"] = {}
            for i, d in enumerate(self.prefix):
                x, c, aux = apply_layer_decode(cfg, d, p["prefix"][str(i)], x,
                                               caches["prefix"][str(i)], pos, aux,
                                               tables=tables, page_size=page_size)
                new["prefix"][str(i)] = c
        if self.reps:
            x, new["blocks"], aux = self._decode_blocks(
                p["blocks"], x, caches["blocks"], pos, aux, tables, page_size)
        if self.suffix:
            new["suffix"] = {}
            for i, d in enumerate(self.suffix):
                x, c, aux = apply_layer_decode(cfg, d, p["suffix"][str(i)], x,
                                               caches["suffix"][str(i)], pos, aux,
                                               tables=tables, page_size=page_size)
                new["suffix"][str(i)] = c
        return x, new, aux

    def _decode_blocks(self, bp, x, caches, pos, aux, tables, page_size):
        """The cycle's decode.  The stacked cache rides in the layer scan's
        carry, whole, and each layer updates its own index of it in place:
        pageable leaves (with ``tables``) go to the layer as the whole pool,
        which it scatters into and gathers from; every other leaf is sliced
        at the layer and written back there.  Scanning the stack as xs and
        re-stacking it as ys would copy all of it each step."""
        cfg = self.cfg
        flags = self.paged_flags()["blocks"]

        def whole(k, n):
            return tables is not None and flags[k][n]

        def body(carry, scanned):
            x, aux, cs = carry
            lp, layer = scanned
            cs = dict(cs)
            for i, d in enumerate(self.cycle):
                k = str(i)
                c = {n: a if whole(k, n)
                     else jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                     for n, a in cs[k].items()}
                x, c, aux = apply_layer_decode(
                    cfg, d, lp[k], x, c, pos, aux,
                    tables=tables, page_size=page_size, layer=layer)
                cs[k] = {n: c[n] if whole(k, n)
                         else jax.lax.dynamic_update_index_in_dim(
                             a, c[n].astype(a.dtype), layer, 0)
                         for n, a in cs[k].items()}
            return (x, aux, cs), None

        (x, aux, caches), _ = jax.lax.scan(
            body, (x, aux, caches),
            (bp, jnp.arange(self.reps, dtype=jnp.int32)))
        return x, caches, aux
