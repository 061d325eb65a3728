"""RG-LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

    r_t = σ(W_a x_t + b_a)                  recurrence gate
    i_t = σ(W_x x_t + b_x)                  input gate
    a_t = exp(-c · softplus(Λ) ⊙ r_t)       per-channel decay
    h_t = a_t ⊙ h_{t-1} + √(1 − a_t²) ⊙ (i_t ⊙ x_t)

Block layout: linear-in (d→w) ∥ gelu gate branch, causal depthwise conv
(width 4), RG-LRU, gated multiply, linear-out (w→d).  The diagonal linear
recurrence is evaluated with ``jax.lax.associative_scan`` during training —
O(log S) depth — and one sequential step during decode (O(1) state: the
reason recurrentgemma-9b runs the long_500k cell).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain_hidden, constrain_residual
from repro.models import common as cm


def rglru_specs(cfg) -> dict:
    d = cfg.d_model
    w = cfg.recurrent.lru_width
    cw = cfg.recurrent.conv_width
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_in": cm.ParamSpec((d, w), ("embed", "mlp"), dt),
        "w_gate_in": cm.ParamSpec((d, w), ("embed", "mlp"), dt),
        "conv_w": cm.ParamSpec((cw, w), ("conv", "mlp"), dt, "small"),
        "conv_b": cm.ParamSpec((w,), ("mlp",), jnp.float32, "zeros"),
        "lam": cm.ParamSpec((w,), ("mlp",), jnp.float32, "decay"),
        "w_a": cm.ParamSpec((w, w), ("mlp", "mlp"), dt, "small"),
        "b_a": cm.ParamSpec((w,), ("mlp",), jnp.float32, "zeros"),
        "w_x": cm.ParamSpec((w, w), ("mlp", "mlp"), dt, "small"),
        "b_x": cm.ParamSpec((w,), ("mlp",), jnp.float32, "zeros"),
        "w_out": cm.ParamSpec((w, d), ("mlp", "embed"), dt),
    }


def _gates(cfg, p, u):
    """u: (..., w) conv output → (log_a, b) of the recurrence h' = a·h + b."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("...w,wv->...v", u, p["w_a"]).astype(jnp.float32)
                       + p["b_a"])
    i = jax.nn.sigmoid(jnp.einsum("...w,wv->...v", u, p["w_x"]).astype(jnp.float32)
                       + p["b_x"])
    log_a = -cfg.recurrent.c * jax.nn.softplus(p["lam"]) * r      # ≤ 0
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)
    return a, b


def _conv_train(p, x):
    """Causal depthwise conv via shifted adds. x: (B,S,w)."""
    cw = p["conv_w"].shape[0]
    y = x * p["conv_w"][cw - 1].astype(x.dtype)
    for i in range(1, cw):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        y = y + shifted * p["conv_w"][cw - 1 - i].astype(x.dtype)
    return y + p["conv_b"].astype(x.dtype)


def rglru_block(cfg, p: dict, x, h0=None, conv_state=None):
    """Full-sequence recurrent block. x: (B,S,d).

    Returns (out, (h_final, conv_tail)) — the state pair primes decode.
    """
    B, S, _ = x.shape
    u = constrain_hidden(jnp.einsum("bsd,dw->bsw", x, p["w_in"]))
    gate = constrain_hidden(cm.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_gate_in"])))
    if conv_state is not None:  # continuation: prepend cached conv tail
        u_ext = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)
        c = _conv_train(p, u_ext)[:, conv_state.shape[1]:]
    else:
        c = _conv_train(p, u)
    a, b = _gates(cfg, p, c)

    if h0 is None:
        h0 = jnp.zeros((B, a.shape[-1]), jnp.float32)
    # prepend the carried state as step 0 with a=1 (identity), b=h0
    a_ext = jnp.concatenate([jnp.ones((B, 1, a.shape[-1]), jnp.float32), a], axis=1)
    b_ext = jnp.concatenate([h0[:, None, :], b], axis=1)

    W = a.shape[-1]
    if (cfg.use_pallas and h0 is not None and S % 64 == 0
            and W % min(128, W) == 0):
        # Pallas sequential-scan kernel; the carried state enters as b_0 of
        # a length-S+? recurrence — fold it into b instead: h_1 = a_1·h0 + b_1
        from repro.kernels import interpret_mode
        from repro.kernels.rglru.ops import linear_recurrence

        b_seeded = b.at[:, 0].add(a[:, 0] * h0)
        h = linear_recurrence(a, b_seeded, chunk=64, block_w=min(128, W),
                              interpret=interpret_mode())
    else:
        def combine(lhs, rhs):
            a1, b1 = lhs
            a2, b2 = rhs
            return a1 * a2, a2 * b1 + b2

        _, h = jax.lax.associative_scan(combine, (a_ext, b_ext), axis=1)
        h = h[:, 1:]                                              # drop seed step
    out = constrain_residual(
        jnp.einsum("bsw,wd->bsd", (h.astype(x.dtype) * gate), p["w_out"]))
    cw = cfg.recurrent.conv_width
    conv_tail = u[:, -(cw - 1):].astype(jnp.float32)
    return out.astype(x.dtype), (h[:, -1], conv_tail)


def rglru_decode(cfg, p: dict, x1, h, conv_state):
    """One-token step. x1: (B,1,d); h: (B,w) fp32; conv_state: (B,cw-1,w)."""
    u = jnp.einsum("bsd,dw->bsw", x1, p["w_in"])                  # (B,1,w)
    gate = cm.gelu(jnp.einsum("bsd,dw->bsw", x1, p["w_gate_in"]))
    window = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)  # (B,cw,w)
    c = jnp.einsum("bcw,cw->bw", window, p["conv_w"]) + p["conv_b"].astype(u.dtype)
    a, b = _gates(cfg, p, c[:, None, :])
    h = (a[:, 0] * h + b[:, 0]).astype(jnp.float32)
    out = jnp.einsum("bw,wd->bd", h.astype(x1.dtype) * gate[:, 0], p["w_out"])
    return out[:, None].astype(x1.dtype), h, window[:, 1:].astype(jnp.float32)
