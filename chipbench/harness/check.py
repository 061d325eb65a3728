"""The output check: served tokens against the plain float32 reference.

After the window has closed, a sample of the finished requests, drawn from
the seed and holding the longest one, is run through the configuration's
reference module once, over each prompt with its served tokens (teacher
forcing).  At every served position the reference's logits give the gap by
which the served token's logit lies below the reference's best; the number
compared is the widest such gap.  Greedy decoding serves the top token, so
a sound program reads only rounding there.

The control puts the reference in the program's place at the next lower
precision: every operand of every matrix product rounded to float8 (e4m3,
one scale per tensor).  It reads, at the same positions, the gap of the
token that the float8 computation puts first.

The reference runs layer by layer (one jitted layer function, the layer an
argument), one sequence at a time, at ``HIGHEST`` matmul precision, with
each sequence padded at its end to ``max_seq`` so that one compile serves
every sample.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
#: the head runs over the padded positions in this many pieces
HEAD_CHUNKS = 4


def q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(control: bool):
    def mm(eq, a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if control:
            a, b = q8(a), q8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


@functools.lru_cache(maxsize=None)
def _programs(fam, cfg_text: str):
    """The jitted pieces for one configuration (its file's text as key)."""
    cfg = json.loads(cfg_text)

    @functools.partial(jax.jit, static_argnums=3)
    def layer(blk, li, x, control):
        p = jax.tree.map(lambda a: a[li].astype(jnp.float32), blk)
        return fam.layer(p, x, cfg, _mm(control))

    @jax.jit
    def embed(w, tokens):
        return fam.embed(w, tokens)

    def chunks(x):                       # bound the (S, vocab) logits
        return x.reshape(HEAD_CHUNKS, -1, *x.shape[1:])

    @jax.jit
    def head(w, h, nxt):
        u = fam.unembed(w).astype(jnp.float32)

        def f(args):
            hc, nc = args
            logits = jnp.einsum("sd,dv->sv", hc, u, precision=HIGHEST)
            served = jnp.take_along_axis(logits, nc[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1) - served

        return jax.lax.map(f, (chunks(fam.final(w, h, cfg)),
                               chunks(nxt))).reshape(-1)

    @jax.jit
    def head_control(w, h_ref, h_ctl):
        u = fam.unembed(w).astype(jnp.float32)
        uq = q8(u)

        def f(args):
            hr, hc = args
            logits = jnp.einsum("sd,dv->sv", hr, u, precision=HIGHEST)
            pick = jnp.argmax(jnp.einsum("sd,dv->sv", hc, uq,
                                         precision=HIGHEST), axis=-1)
            return (jnp.max(logits, axis=-1)
                    - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0])

        return jax.lax.map(f, (chunks(fam.final(w, h_ref, cfg)),
                               chunks(q8(fam.final(w, h_ctl, cfg))))
                           ).reshape(-1)

    return layer, embed, head, head_control


def gaps(fam, cfg: Dict, weights, seqs: Sequence[Tuple[List[int], int]],
         pad_to: int, control: bool = False) -> List[Dict]:
    """For each ``(tokens, n_prompt)``: the gaps at each served position,
    ``{"served": ..., "control": ...}``, the second with ``control`` only
    (the gap of the token the float8 computation puts first)."""
    layer, embed, head, head_control = _programs(
        fam, json.dumps(cfg, sort_keys=True))
    blk = fam.blocks(weights)
    L = cfg["arch"]["num_layers"]
    out = []
    with jax.default_matmul_precision("highest"):
        for toks, n_prompt in seqs:
            n = len(toks)
            padded = np.zeros(pad_to, np.int32)
            padded[:n] = toks
            x = embed(weights, jnp.asarray(padded))
            xc = x
            for li in range(L):
                x = layer(blk, jnp.int32(li), x, False)
                if control:
                    xc = layer(blk, jnp.int32(li), xc, True)
            nxt = np.zeros(pad_to, np.int32)
            nxt[:n - 1] = toks[1:]
            # position t predicts token t + 1: served tokens are n_prompt..
            span = slice(n_prompt - 1, n - 1)
            g = {"served": np.asarray(head(weights, x, jnp.asarray(nxt)))[span]}
            if control:
                g["control"] = np.asarray(head_control(weights, x, xc))[span]
            out.append(g)
    return out


def sample(records: List[Dict], seed: int, n: int) -> List[Dict]:
    """``n`` finished requests: the longest (prompt plus output) and the
    rest drawn from the seed."""
    done = [r for r in records if r.get("status") == "completed"
            and r.get("tokens")]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["prompt"]) + len(r["tokens"]),
                                       r["i"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    return [longest] + [rest[j] for j in rng.permutation(len(rest))[:n - 1]]
