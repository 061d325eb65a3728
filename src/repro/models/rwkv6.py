"""RWKV-6 "Finch" time-mix (arXiv:2404.05892) — data-dependent decay.

Recurrence per head (state S ∈ R^{hd×hd}, fp32):

    S_t = diag(w_t) · S_{t-1} + k_tᵀ v_t
    y_t = r_t · (S_{t-1} + diag(u) · k_tᵀ v_t)

with per-channel, per-token decay  w_t = exp(-exp(w0 + lora_w(x̃_t))) ∈ (0,1).

Training uses the *chunked* parallel form (chunk length ``CHUNK``): within a
chunk the pairwise decay exponent  cum_{t-1} − cum_j  (j < t) is materialized
explicitly — it is always ≤ 0, so ``exp`` never overflows; this is the
numerically-exact variant of the flash-linear-attention chunked algorithm and
is also the oracle for the Pallas kernel (``repro.kernels.rwkv6``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain_qkv, constrain_residual
from repro.models import common as cm

CHUNK = 32
_MIX = 5  # w, k, v, r, g


def rwkv_specs(cfg) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    hd = cfg.rwkv.head_dim
    dl, ml, gl = cfg.rwkv.decay_lora, cfg.rwkv.mix_lora, cfg.rwkv.gate_lora
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "mu_x": cm.ParamSpec((d,), ("embed",), jnp.float32, "small"),
        "mu_5": cm.ParamSpec((_MIX, d), (None, "embed"), jnp.float32, "small"),
        "tm_w1": cm.ParamSpec((d, _MIX * ml), ("embed", "lora"), dt),
        "tm_w2": cm.ParamSpec((_MIX, ml, d), (None, "lora", "embed"), dt, "small"),
        "w0": cm.ParamSpec((d,), ("embed",), jnp.float32, "decay"),
        "td_w1": cm.ParamSpec((d, dl), ("embed", "lora"), dt),
        "td_w2": cm.ParamSpec((dl, d), ("lora", "embed"), dt, "small"),
        "u": cm.ParamSpec((h, hd), ("heads", None), jnp.float32, "small"),
        "w_r": cm.ParamSpec((d, h, hd), ("embed", "heads", None), dt),
        "w_k": cm.ParamSpec((d, h, hd), ("embed", "heads", None), dt),
        "w_v": cm.ParamSpec((d, h, hd), ("embed", "heads", None), dt),
        "w_g": cm.ParamSpec((d, gl), ("embed", "lora"), dt),
        "w_g2": cm.ParamSpec((gl, h, hd), ("lora", "heads", None), dt),
        "ln_x": cm.ParamSpec((h, hd), ("heads", None), jnp.float32, "zeros"),
        "ln_x_b": cm.ParamSpec((h, hd), ("heads", None), jnp.float32, "zeros"),
        "w_o": cm.ParamSpec((h, hd, d), ("heads", None, "embed"), dt),
    }


def _projections(cfg, p, x, x_prev):
    """Token-shift mixing + r/k/v/g/decay projections.

    x, x_prev: (B, S, d).  Returns r,k,v,g: (B,S,H,hd); lw: (B,S,H,hd) fp32
    (log-decay, ≤ 0).
    """
    B, S, d = x.shape
    h, hd = cfg.num_heads, cfg.rwkv.head_dim
    sx = (x_prev - x).astype(x.dtype)
    xx = x + sx * p["mu_x"].astype(x.dtype)
    m = jnp.tanh(jnp.einsum("bsd,dl->bsl", xx, p["tm_w1"]))
    m = m.reshape(B, S, _MIX, -1)
    deltas = jnp.einsum("bsfl,fld->bsfd", m, p["tm_w2"])          # (B,S,5,d)
    mixed = x[:, :, None, :] + sx[:, :, None, :] * (
        p["mu_5"].astype(x.dtype)[None, None] + deltas)
    xw, xk, xv, xr, xg = [mixed[:, :, i] for i in range(_MIX)]

    r = constrain_qkv(jnp.einsum("bsd,dhk->bshk", xr, p["w_r"]))
    k = constrain_qkv(jnp.einsum("bsd,dhk->bshk", xk, p["w_k"]))
    v = constrain_qkv(jnp.einsum("bsd,dhk->bshk", xv, p["w_v"]))
    g = jax.nn.silu(jnp.einsum("bsl,lhk->bshk", jnp.tanh(
        jnp.einsum("bsd,dl->bsl", xg, p["w_g"])), p["w_g2"]))
    w_raw = p["w0"].astype(jnp.float32) + jnp.einsum(
        "bsl,ld->bsd", jnp.tanh(jnp.einsum("bsd,dl->bsl", xw, p["td_w1"])),
        p["td_w2"]).astype(jnp.float32)
    lw = -jnp.exp(w_raw).reshape(B, S, h, hd)                     # log w_t ≤ 0
    return r, k, v, g, lw


def _chunk_scan(r, k, v, lw, u, state):
    """Chunked linear recurrence.  r,k,v: (B,S,H,hd) compute dtype;
    lw: (B,S,H,hd) fp32; u: (H,hd); state: (B,H,hd,hd) fp32."""
    B, S, H, hd = r.shape
    C = CHUNK if S % CHUNK == 0 else (S if S < CHUNK else 1)
    n = S // C
    rf = r.astype(jnp.float32).reshape(B, n, C, H, hd).transpose(1, 0, 2, 3, 4)
    kf = k.astype(jnp.float32).reshape(B, n, C, H, hd).transpose(1, 0, 2, 3, 4)
    vf = v.astype(jnp.float32).reshape(B, n, C, H, hd).transpose(1, 0, 2, 3, 4)
    lwf = lw.reshape(B, n, C, H, hd).transpose(1, 0, 2, 3, 4)

    tri = jnp.tril(jnp.ones((C, C), jnp.bool_), k=-1)             # strict lower

    def body(S_c, blk):
        rc, kc, vc, lwc = blk                                     # (B,C,H,hd)
        cum = jnp.cumsum(lwc, axis=1)                             # inclusive
        # pairwise exponent cum_{t-1} - cum_j  (t > j): always ≤ 0
        expn = (cum - lwc)[:, :, None] - cum[:, None, :]          # (B,t,j,H,hd)
        expn = jnp.where(tri[None, :, :, None, None], expn, -jnp.inf)
        pair = jnp.exp(expn)
        A = jnp.einsum("bthd,btjhd,bjhd->bhtj", rc, pair, kc)
        diag = jnp.einsum("bthd,hd,bthd->bth", rc, u, kc)
        A = A + jnp.einsum("bth,tj->bhtj", diag, jnp.eye(C, dtype=jnp.float32))
        y = jnp.einsum("bhtj,bjhd->bthd", A, vc)
        # cross-chunk read: r_t decayed to chunk start
        y = y + jnp.einsum("bthd,bhde->bthe", rc * jnp.exp(cum - lwc), S_c)
        # state update
        dec_k = jnp.exp(cum[:, -1:, :, :] - cum)                  # ≤ 1
        S_n = S_c * jnp.exp(cum[:, -1])[:, :, :, None] + jnp.einsum(
            "bjhd,bjhe->bhde", kc * dec_k, vc)
        return S_n, y

    # recompute the pairwise-decay block in backward (it dwarfs r/k/v)
    body = jax.checkpoint(body)
    state, ys = jax.lax.scan(body, state, (rf, kf, vf, lwf))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    return y.astype(r.dtype), state


def _readout(cfg, p, y, g, x_dtype):
    """Per-head groupnorm → gate → output projection."""
    yf = y.astype(jnp.float32)
    mu = jnp.mean(yf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(yf - mu), axis=-1, keepdims=True)
    yn = (yf - mu) * jax.lax.rsqrt(var + 64e-5)
    yn = yn * (1.0 + p["ln_x"]) + p["ln_x_b"]
    out = (yn.astype(x_dtype) * g.astype(x_dtype))
    y = jnp.einsum("bshk,hkd->bsd", out, p["w_o"]).astype(x_dtype)
    return constrain_residual(y) if y.ndim == 3 else y


def rwkv_time_mix(cfg, p: dict, x, x_prev=None, state=None,
                  want_state: bool = True):
    """Full-sequence time-mix. Returns (out, final_state, last_x).

    ``want_state=False`` (train path — the final state is discarded) allows
    routing through the Pallas chunked-recurrence kernel when enabled.
    """
    B, S, d = x.shape
    h, hd = cfg.num_heads, cfg.rwkv.head_dim
    if x_prev is None:
        x_prev_seq = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:  # continuing from a cached last token
        x_prev_seq = jnp.concatenate([x_prev[:, None, :], x[:, :-1]], axis=1)
    r, k, v, g, lw = _projections(cfg, p, x, x_prev_seq)
    if state is None:
        state = jnp.zeros((B, h, hd, hd), jnp.float32)
    use_kernel = (cfg.use_pallas and not want_state and S % CHUNK == 0
                  and x_prev is None)
    if use_kernel:
        from repro.kernels import interpret_mode
        from repro.kernels.rwkv6.ops import time_mix_scan

        y = time_mix_scan(r, k, v, lw, p["u"].astype(jnp.float32),
                          chunk=CHUNK, interpret=interpret_mode())
    else:
        y, state = _chunk_scan(r, k, v, lw, p["u"].astype(jnp.float32), state)
    return _readout(cfg, p, y, g, x.dtype), state, x[:, -1]


def rwkv_decode(cfg, p: dict, x1, state, x_prev):
    """Single-token decode. x1: (B,1,d); state: (B,H,hd,hd) fp32; x_prev: (B,d)."""
    B = x1.shape[0]
    h, hd = cfg.num_heads, cfg.rwkv.head_dim
    r, k, v, g, lw = _projections(cfg, p, x1, x_prev[:, None, :])
    rf, kf, vf = (t.astype(jnp.float32)[:, 0] for t in (r, k, v))  # (B,H,hd)
    w = jnp.exp(lw[:, 0])                                          # (B,H,hd)
    u = p["u"].astype(jnp.float32)
    kv = jnp.einsum("bhd,bhe->bhde", kf, vf)
    y = jnp.einsum("bhd,bhde->bhe", rf, state + u[None, :, :, None] * kv)
    state = state * w[..., None] + kv
    out = _readout(cfg, p, y[:, None].astype(x1.dtype), g, x1.dtype)
    return out, state, x1[:, 0]
