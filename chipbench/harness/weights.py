"""Weights drawn on the device from a seed, in one jitted call.

The tree's layout and dtypes come from the program (its abstract param
tree); the values come from here, by laws the configuration's reference
module gives per leaf name.  Each leaf has its own key, folded from the
seed and the leaf's path, so a leaf's values do not depend on the others.
Leaves stacked over layers are drawn one layer at a time (``lax.map``), so
the float32 temporaries stay one layer's size.
"""
from __future__ import annotations

import zlib
from typing import Callable, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any non-negative whole number (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _sample(key, law: Tuple, shape, dtype):
    kind = law[0]
    if kind == "normal":
        x = law[1] * jax.random.normal(key, shape, jnp.float32)
    elif kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, law[1], law[2])
    elif kind == "loglog":
        # log(-log(a)) for a uniform in (lo, hi): a decay exp(-exp(x)) = a
        a = jax.random.uniform(key, shape, jnp.float32, law[1], law[2])
        x = jnp.log(-jnp.log(a))
    else:
        raise ValueError(f"unknown law {law!r}")
    return x.astype(dtype)


def draw_tree(shapes, seed: int, law: Callable[[str, Tuple], Tuple]):
    """Arrays shaped like ``shapes`` (a ShapeDtypeStruct tree).  ``law``
    maps (leaf name, per-layer shape) to ``("normal", std)``,
    ``("uniform", lo, hi)`` or ``("loglog", lo, hi)``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = []
    for path, s in flat:
        name = path_name(path)
        stacked = "/blocks/" in f"/{name}/"
        leaf = name.rsplit("/", 1)[-1]
        # norm parameters sit under e.g. ln1/scale: name them by the parent
        if leaf in ("scale", "bias"):
            leaf = "/".join(name.rsplit("/", 2)[-2:])
        shape = tuple(s.shape[1:] if stacked else s.shape)
        specs.append((name, stacked, s.shape, s.dtype, law(leaf, shape)))

    def make(key):
        out = []
        for name, stacked, shape, dtype, lw in specs:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if stacked:
                ks = jax.random.split(k, shape[0])
                out.append(jax.lax.map(
                    lambda kk, lw=lw, sh=shape[1:], dt=dtype:
                    _sample(kk, lw, sh, dt), ks))
            else:
                out.append(_sample(k, lw, shape, dtype))
        return out

    leaves = jax.jit(make)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
