"""RWKV-6 "Finch" (arXiv:2404.05892): plain float32 reference, weight laws
and operation counts.

The block, as published, with the token shift ``sx_t = h_{t-1} - h_t``
(``h_{-1} = 0``) of the block's normalised input ``h``:

- ``ln0``, a LayerNorm, on the embedding before the first block;
- time mix on ``h = LN1(x)``: the ddlerp ``xxx = h + sx * mu_x``,
  ``(m_w, m_k, m_v, m_r, m_g) = tanh(xxx A) B`` (rank ``mix_lora``),
  ``x_i = h + sx * (mu_i + m_i)``; the decay
  ``w_t = exp(-exp(w0 + tanh(x_w D1) D2))`` (rank ``decay_lora``); the
  projections ``r, k, v = x_r W_r, x_k W_k, x_v W_v`` and the full-rank gate
  ``g = silu(x_g W_g)``; per head the recurrence
  ``y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)``,
  ``S_t = diag(w_t) S_{t-1} + k_t^T v_t``, token by token from ``S = 0``;
  the readout ``W_o (GroupNorm_H(y; eps 64e-5) * g)``; a residual;
- channel mix on ``h = LN2(x)``: ``sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v)``
  with ``x_k, x_r`` token-shift mixes of ``h``; a residual;
- a final LayerNorm and an untied output head.

Everything here is float32.  Every matrix product runs through ``mm``, at
``HIGHEST`` precision; the recurrence is elementwise float32, a scan over
tokens, and shares nothing with the program's chunked form.  Departures,
each also in the configuration file:

- a norm's weight is stored as an offset from one, ``x_hat * (1 + w) + b``:
  the same function under another parametrisation, kept so that the
  weights the benchmark draws mean the same here and in the program;
- ``rescale_every`` is not modelled: it halves the residual every six
  blocks in fp16 inference to keep it from overflowing, which the layer
  norms undo up to their epsilon.

It imports nothing of the program.  It reads the weights by the names of
the program's tree (``decoder/blocks/0/...``, layers stacked on axis 0).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import draw_tree

#: the published ``layer_norm_epsilon``, and GroupNorm's ``1e-5 *
#: head_size_divisor ** 2`` (divisor 8)
LN_EPS = 1e-5
GN_EPS = 64e-5

#: token-shift mixes, by the order of ``(m_w, m_k, m_v, m_r, m_g)``
MIXES = ("w", "k", "v", "r", "g")

_PROJECTIONS = {"tm_w1", "td_w1", "w_r", "w_k", "w_v", "w_g", "unembed"}


def law(name: str, shape):
    """The law of each leaf, by name and per-layer shape."""
    if name.endswith(("/scale", "/bias")) or name in ("ln_x", "ln_x_b"):
        return ("normal", 0.1)
    if name == "embed":
        return ("normal", 1.0)
    if name in ("mu_x", "mu_5", "mu_k", "mu_r", "u"):
        return ("uniform", 0.0, 1.0)
    if name == "w0":
        # per-channel decays exp(-exp(w0)) between 0.5 and 0.999
        return ("loglog", 0.5, 0.999)
    if name == "tm_w2":                           # (5, mix_lora, d)
        return ("normal", 0.1 / math.sqrt(shape[1]))
    if name == "td_w2":                           # (decay_lora, d)
        return ("normal", 0.1 / math.sqrt(shape[0]))
    if name == "w_o":                             # (heads, head_dim, d)
        return ("normal", 1.0 / math.sqrt(shape[0] * shape[1]))
    if name == "w_g" and len(shape) != 3:
        raise ValueError(f"gate of shape {shape}: Finch's gate is a full "
                         "(d, heads, head_dim) projection")
    if name in _PROJECTIONS:                      # (fan_in, ...)
        return ("normal", 1.0 / math.sqrt(shape[0]))
    raise ValueError(f"no law for leaf {name!r}: not an RWKV-6 weight")


def draw(shapes, seed: int):
    if "ln0" not in shapes:
        raise ValueError("the program's tree has no ln0: RWKV-6 normalises "
                         "the embedding before the first block")
    return draw_tree(shapes, seed, law)


# -- forward -----------------------------------------------------------------

def _ln(x, p, eps=LN_EPS):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"]) + p["bias"]


def _shift(h):
    """The previous token's row of ``h``, zero before the first."""
    return jnp.concatenate([jnp.zeros_like(h[:1]), h[:-1]], axis=0)


def wkv(r, k, v, w, u, state):
    """The recurrence, token by token. ``r, k, v, w``: (S, H, hd), ``w``
    the decay in (0, 1); ``u``: (H, hd); ``state``: (H, hd, hd).  Returns
    ``y`` (S, H, hd) and the final state."""

    def step(s, rkvw):
        rt, kt, vt, wt = rkvw
        kv = kt[:, :, None] * vt[:, None, :]
        y = jnp.sum(rt[:, :, None] * (s + u[:, :, None] * kv), axis=1)
        return wt[:, :, None] * s + kv, y

    state, y = jax.lax.scan(step, state, (r, k, v, w))
    return y, state


def blocks(w):
    """The layer-stacked part of the weights."""
    return w["decoder"]["blocks"]["0"]


def embed(w, tokens):
    ln0 = jax.tree.map(lambda a: a.astype(jnp.float32), w["ln0"])
    return _ln(w["embed"][tokens].astype(jnp.float32), ln0)


def time_mix(m, h, mm):
    """One time mix on one sequence. ``m``: the mixer's weights (float32);
    ``h``: (S, d), the normalised input."""
    S, d = h.shape
    H, hd = m["u"].shape
    sx = _shift(h) - h
    xxx = h + sx * m["mu_x"]
    lora = jnp.tanh(mm("sd,dl->sl", xxx, m["tm_w1"])).reshape(
        S, len(MIXES), -1)
    deltas = mm("sfl,fld->sfd", lora, m["tm_w2"])
    x = {n: h + sx * (m["mu_5"][i] + deltas[:, i])
         for i, n in enumerate(MIXES)}
    r = mm("sd,dhk->shk", x["r"], m["w_r"])
    k = mm("sd,dhk->shk", x["k"], m["w_k"])
    v = mm("sd,dhk->shk", x["v"], m["w_v"])
    g = jax.nn.silu(mm("sd,dhk->shk", x["g"], m["w_g"]))
    decay = m["w0"] + mm("sl,ld->sd", jnp.tanh(
        mm("sd,dl->sl", x["w"], m["td_w1"])), m["td_w2"])
    w = jnp.exp(-jnp.exp(decay)).reshape(S, H, hd)
    y, _ = wkv(r, k, v, w, m["u"], jnp.zeros((H, hd, hd), jnp.float32))
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + GN_EPS) * (1.0 + m["ln_x"]) \
        + m["ln_x_b"]
    return mm("shk,hkd->sd", y * g, m["w_o"])


def channel_mix(f, h, mm):
    sx = _shift(h) - h
    k = jnp.square(jax.nn.relu(mm("sd,df->sf", h + sx * f["mu_k"],
                                  f["w_k"])))
    r = jax.nn.sigmoid(mm("sd,de->se", h + sx * f["mu_r"], f["w_r"]))
    return r * mm("sf,fd->sd", k, f["w_v"])


def layer(p, x, cfg, mm):
    """One block on one sequence. ``p``: this layer's weights in float32;
    ``x``: (S, d); ``mm(eq, a, b)``: the matrix product."""
    x = x + time_mix(p["mixer"], _ln(x, p["ln1"]), mm)
    return x + channel_mix(p["ffn"], _ln(x, p["ln2"]), mm)


def final(w, x, cfg):
    return _ln(x, jax.tree.map(lambda a: a.astype(jnp.float32),
                               w["final_norm"]))


def unembed(w):
    return w["unembed"]


# -- operations and bytes, from shapes -----------------------------------------

def _dims(cfg):
    a = cfg["arch"]
    r = a["rwkv"]
    return (a["d_model"], a["num_heads"], r["head_dim"], a["d_ff"],
            a["vocab_size"], a["num_layers"], r["mix_lora"], r["decay_lora"])


def _matrix_params(cfg) -> int:
    """One block's matrix weights, each multiplied by every token."""
    d, H, hd, f, _, _, ml, dl = _dims(cfg)
    return (5 * d * H * hd                        # r, k, v, g, o
            + 2 * d * len(MIXES) * ml + 2 * d * dl  # token-shift, decay LoRA
            + 2 * d * f + d * d)                  # channel mix


def _vector_params(cfg) -> int:
    """One block's float32 vectors: mu_x, mu_5, w0, u, ln_x (scale and
    bias), mu_k, mu_r, ln1 and ln2 (scale and bias)."""
    d, H, hd, *_ = _dims(cfg)
    return (1 + len(MIXES) + 1 + 2 + 4) * d + 3 * H * hd


def param_count(cfg) -> int:
    d, _, _, _, V, L, _, _ = _dims(cfg)
    return L * (_matrix_params(cfg) + _vector_params(cfg)) + 2 * V * d + 4 * d


def _weight_bytes(cfg) -> int:
    """Weights a step reads: the blocks (bf16 matrices, float32 vectors),
    the final norm and the head; not the embedding table."""
    d, _, _, _, V, L, _, _ = _dims(cfg)
    return (L * (2 * _matrix_params(cfg) + 4 * _vector_params(cfg))
            + 8 * d + 2 * d * V)


def state_bytes_per_row(cfg) -> int:
    """Recurrent state one row holds: per block the float32 WKV state of
    every head and the two bf16 token-shift rows."""
    d, H, hd, _, _, L, _, _ = _dims(cfg)
    return L * (H * hd * hd * 4 + 2 * d * 2)


def _recurrence_flops(cfg) -> int:
    """One token through one block's recurrence."""
    _, H, hd, *_ = _dims(cfg)
    return 4 * H * hd * hd


def prefill_cost(cfg, S: int):
    """(FLOPs, bytes) of one B=1 prefill of S tokens: every projection on
    every token, the recurrence on every token, and the head on the last
    token only; the weights read once and one row's state written."""
    d, _, _, _, V, L, _, _ = _dims(cfg)
    flops = (S * L * (2 * _matrix_params(cfg) + _recurrence_flops(cfg))
             + 2 * d * V)
    return flops, _weight_bytes(cfg) + state_bytes_per_row(cfg)


def decode_cost(cfg, contexts):
    """(FLOPs, bytes) of one decode step over the live rows (``contexts``:
    their positions, which a recurrence does not read): weights read once,
    each live row's embedding row read and its state read and written."""
    d, _, _, _, V, L, _, _ = _dims(cfg)
    n = len(contexts)
    flops = n * (L * (2 * _matrix_params(cfg) + _recurrence_flops(cfg))
                 + 2 * d * V)
    byts = _weight_bytes(cfg) + n * (d * 2 + 2 * state_bytes_per_row(cfg))
    return flops, byts


def flash_cost(cfg, S: int):
    """No attention, so no flash-attention call."""
    return 0, 0


def flash_calls_per_prefill(cfg) -> int:
    return 0
