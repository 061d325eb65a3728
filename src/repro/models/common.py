"""Model building blocks shared across architectures.

Parameter system
----------------
Models are pure-functional: a model definition produces a pytree of
:class:`ParamSpec` leaves (shape, dtype, *logical axes*, initializer).
``init_params`` materializes the tree; ``logical_axes`` extracts the parallel
axes tree which ``repro.distributed.sharding`` maps onto a mesh via a
:class:`~repro.distributed.sharding.ShardingRecipe`.

Logical axis names used throughout:

- ``"vocab"``   — embedding-table rows / logits dim  → tensor-parallel axis
- ``"embed"``   — d_model dim of weight matrices     → FSDP axis
- ``"heads"``   — attention heads                    → tensor-parallel axis
- ``"kv_heads"``— KV heads (GQA)                     → tensor-parallel axis
- ``"mlp"``     — FFN hidden dim                     → tensor-parallel axis
- ``"expert"``  — MoE expert index                   → expert-parallel axis
- ``"qkv"``, ``"lora"``, ``"conv"``, ``None``        — unsharded small dims
- ``"layers"``  — scan-stacked layer dim             — never sharded
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"             # normal | zeros | ones | decay | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_specs(tree):
    return jax.tree.leaves(tree, is_leaf=is_spec)


def _fan_in(spec: ParamSpec) -> int:
    """Inputs each output of the leaf's contraction sums over.  Head-shaped
    projections contract more than ``shape[-2]``: ``(d, heads, head_dim)``
    sums over ``d``, ``(heads, head_dim, d)`` over ``heads * head_dim``;
    scan-stacked ``layers`` and ``expert`` axes contract nothing."""
    dims = [(n, a) for n, a in zip(spec.shape, spec.axes)
            if a not in ("layers", "expert")]
    names = [a for _, a in dims]
    for head_axis in ("heads", "kv_heads"):
        if head_axis in names:
            i = names.index(head_axis)
            if i == 0:
                return dims[0][0] * dims[1][0]
            return math.prod(n for n, _ in dims[:i])
    return spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]


def _init_leaf(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "decay":
        # log-decay init for recurrences: a in (0.9, 0.999)
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        return jnp.log(-jnp.log(u)).astype(spec.dtype)
    std = spec.scale / math.sqrt(max(_fan_in(spec), 1))
    if spec.init == "small":
        std = 0.02 * spec.scale
    return (std * jax.random.normal(key, spec.shape, jnp.float32)).astype(spec.dtype)


#: one compiled draw per distinct spec; it returns ``spec.dtype``, so a bf16
#: leaf is built on the device without a float32 copy of the whole leaf
_draw_leaf = jax.jit(_init_leaf, static_argnums=1)


def init_params(specs, seed: int = 0):
    """Materialize a ParamSpec pytree into arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    root = jax.random.PRNGKey(seed)
    keys = jax.random.split(root, len(leaves))
    vals = [_draw_leaf(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(specs):
    """ShapeDtypeStruct tree for AOT lowering — never allocates."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
                        is_leaf=is_spec)


def logical_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def param_count(specs) -> int:
    return int(sum(np.prod(s.shape) for s in tree_specs(specs)))


# ---------------------------------------------------------------------------
# numerics


def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x, w, b=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(dt)


def norm_spec(cfg, dim: int, axes=("embed",)) -> dict:
    s = {"scale": ParamSpec((dim,), axes, jnp.float32, "zeros")}
    if cfg.norm == "layernorm":
        s["bias"] = ParamSpec((dim,), axes, jnp.float32, "zeros")
    return s


def apply_norm(cfg, p: dict, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p.get("bias"))
    return rmsnorm(x, p["scale"])


def rope(x, positions, theta: float = 10000.0, rotary_dim: Optional[int] = None):
    """Rotary position embedding over the trailing head-dim.

    x: (..., seq, heads, head_dim) or (..., seq, head_dim); positions:
    (seq,) shared across the batch, or (batch, seq) when each row sits on
    its own timeline (continuous batching).
    """
    hd = x.shape[-1]
    rd = rotary_dim or hd
    half = rd // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    positions = jnp.atleast_1d(positions)
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., seq, half)
    if x.ndim == 4:                                                # (B, S, H, hd)
        ang = ang[..., None, :]                                    # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rd]
    xr = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if rd < hd:
        xr = jnp.concatenate([xr, x[..., rd:]], axis=-1)
    return xr.astype(x.dtype)


def dense_spec(d_in: int, d_out: int, axes, dtype, bias: bool = False,
               bias_axis: Optional[str] = None, init: str = "normal",
               scale: float = 1.0) -> dict:
    s = {"kernel": ParamSpec((d_in, d_out), axes, dtype, init, scale)}
    if bias:
        s["bias"] = ParamSpec((d_out,), (bias_axis,), jnp.float32, "zeros")
    return s


def dense(p: dict, x, dims: str = "...a,ab->...b"):
    y = jnp.einsum(dims, x, p["kernel"])
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS: dict = {
    "gelu": gelu,
    "silu": jax.nn.silu,
    "squared_relu": lambda x: jnp.square(jax.nn.relu(x)),
    "relu": jax.nn.relu,
}


def remat_policy(name: str):
    """Map config remat names to jax checkpoint policies (hillclimb axis)."""
    cp = jax.checkpoint_policies
    return {
        "nothing": None,                              # no remat
        "dots": cp.checkpoint_dots,                   # save matmul outputs
        "dots_no_batch": cp.checkpoint_dots_with_no_batch_dims,
        "full": cp.nothing_saveable,                  # recompute everything
        # save the EP-exchanged buffers + expert-GEMM hidden so backward
        # neither re-runs the all_to_alls nor re-gathers expert weights
        "moe": cp.save_only_these_names("moe_bufe", "moe_h"),
    }[name]


def maybe_remat(fn, policy_name: str):
    if policy_name == "nothing":
        return fn
    return jax.checkpoint(fn, policy=remat_policy(policy_name))
