"""InternLM2 (arXiv:2403.17297): plain float32 reference, weight laws and
operation counts.

The decoder layer, as published: pre-RMSNorm, grouped-query attention
with rotary embeddings (rotate-half form), a residual, pre-RMSNorm, a
SwiGLU feed-forward, a residual; a final RMSNorm and an untied output
head.  Everything here is float32 and every matrix product runs at
``HIGHEST`` precision.  Departures, each also in the configuration file:

- a norm's weight is stored as an offset from one, ``x * (1 + w)``: the
  same function under another parametrisation, kept so that the weights
  the benchmark draws mean the same here and in the program;
- no dynamic NTK rope scaling: it only acts past 32768 positions.

It imports nothing of the program.  It reads the weights by the names of
the program's tree (``decoder/blocks/0/...``, layers stacked on axis 0).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import draw_tree

HIGHEST = jax.lax.Precision.HIGHEST


def law(name: str, shape):
    """The law of each leaf, by name and per-layer shape."""
    if name.endswith("/scale"):
        return ("normal", 0.1)
    if name == "embed":
        return ("normal", 1.0)
    if name == "wo":
        return ("normal", 1.0 / math.sqrt(shape[0] * shape[1]))
    # every other leaf is a (fan_in, ...) projection
    return ("normal", 1.0 / math.sqrt(shape[0]))


def draw(shapes, seed: int):
    return draw_tree(shapes, seed, law)


# -- forward -----------------------------------------------------------------

def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def blocks(w):
    """The layer-stacked part of the weights."""
    return w["decoder"]["blocks"]["0"]


def embed(w, tokens):
    return w["embed"][tokens].astype(jnp.float32)


def layer(p, x, cfg, mm):
    """One decoder layer on one sequence. ``p``: this layer's weights in
    float32; ``x``: (S, d); ``mm(eq, a, b)``: the matrix product."""
    a = cfg["arch"]
    eps = cfg["rms_norm_eps"]
    S = x.shape[0]
    H, K = a["num_heads"], a["num_kv_heads"]
    hd = a["d_model"] // H
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"]["scale"], eps)
    m = p["mixer"]
    q = _rope(mm("sd,dhk->shk", h, m["wq"]), pos, a["rope_theta"])
    k = _rope(mm("sd,dgk->sgk", h, m["wk"]), pos, a["rope_theta"])
    v = mm("sd,dgk->sgk", h, m["wv"])
    q = q.reshape(S, K, H // K, hd)                  # head = kv * G + g
    causal = pos[None, :] <= pos[:, None]

    def group(qkv):                                  # one KV head at a time
        qg, kg, vg = qkv                             # (S, G, hd), (S, hd) x2
        s = mm("sqk,tk->qst", qg, kg) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return mm("qst,tk->sqk", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2)))   # (K, S, G, hd)
    o = o.transpose(1, 0, 2, 3).reshape(S, H, hd)
    x = x + mm("shk,hkd->sd", o, m["wo"])
    h = _rms(x, p["ln2"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(mm("sd,df->sf", h, f["w_gate"])) * mm("sd,df->sf", h,
                                                           f["w_up"])
    return x + mm("sf,fd->sd", g, f["w_down"])


def final(w, x, cfg):
    return _rms(x, w["final_norm"]["scale"].astype(jnp.float32),
                cfg["rms_norm_eps"])


def unembed(w):
    return w["unembed"]


# -- operations and bytes, from shapes -----------------------------------------

def _dims(cfg):
    a = cfg["arch"]
    d, H, K = a["d_model"], a["num_heads"], a["num_kv_heads"]
    hd = d // H
    return a, d, H, K, hd, a["d_ff"], a["vocab_size"], a["num_layers"]


def layer_params(cfg) -> int:
    _, d, H, K, hd, f, _, _ = _dims(cfg)
    return 2 * d * H * hd + 2 * d * K * hd + 3 * d * f + 2 * d


def param_count(cfg) -> int:
    _, d, _, _, _, _, V, L = _dims(cfg)
    return L * layer_params(cfg) + 2 * V * d + d


def state_bytes_per_row(cfg, ctx: int) -> int:
    """KV bytes one row holds at ``ctx`` tokens (bf16)."""
    _, _, _, K, hd, _, _, L = _dims(cfg)
    return ctx * L * 2 * K * hd * 2


def prefill_cost(cfg, S: int):
    """(FLOPs, bytes) of one B=1 prefill of S tokens: every layer on every
    token, causal attention, and the head on the last token only."""
    _, d, H, K, hd, f, V, L = _dims(cfg)
    mm = 2 * d * H * hd + 2 * d * K * hd + 3 * d * f
    flops = 2 * S * L * mm + L * 2 * H * hd * S * (S + 1) + 2 * d * V
    byts = 2 * (L * layer_params(cfg) + d * V) + state_bytes_per_row(cfg, S)
    return flops, byts


def decode_cost(cfg, contexts):
    """(FLOPs, bytes) of one decode step over live rows whose tokens sit at
    ``contexts`` (each attends over ctx + 1 keys): weights read once, each
    row's KV read, one token's KV written per row."""
    _, d, H, K, hd, f, V, L = _dims(cfg)
    mm = 2 * d * H * hd + 2 * d * K * hd + 3 * d * f
    n = len(contexts)
    keys = sum(int(c) + 1 for c in contexts)
    flops = n * (2 * L * mm + 2 * d * V) + L * 4 * H * hd * keys
    byts = (2 * (L * layer_params(cfg) + d * V) + n * d * 2
            + state_bytes_per_row(cfg, keys) + state_bytes_per_row(cfg, n))
    return flops, byts


def flash_cost(cfg, S: int):
    """(FLOPs, bytes) of one causal flash-attention call (one layer) over
    S tokens: QK and PV over the S(S+1)/2 causal pairs; q, k, v read and
    o written once, bf16."""
    _, _, H, K, hd, _, _, _ = _dims(cfg)
    return 2 * H * hd * S * (S + 1), 2 * S * hd * (2 * H + 2 * K)


def flash_calls_per_prefill(cfg) -> int:
    return cfg["arch"]["num_layers"]
