"""RWKV-6 "Finch" served by the engine against the plain float32 reference
(``chipbench/reference/rwkv6.py``), at a tiny size on seeded random weights,
comparing logits and not tokens.

Everything here is float32 with matrix products at ``HIGHEST``, so the
program and the reference differ only in the order of their sums: the
program's chunked WKV scan (``_chunk_scan``) against the reference's scan
token by token, and its batched projections against the reference's
per-sequence ones.  That leaves float32 rounding, under 1e-6 of the scale
of the logits (or of the state) compared; each tolerance below is 1e-4 of
that scale, far under the effect of any change to the mathematics (the
perturbation of ``W_g`` or ``ln0`` below moves the logits by more than 1e-2).
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.harness import program  # noqa: E402
from chipbench.reference import rwkv6 as ref  # noqa: E402
from repro.models import (build_prefill_step, decode_cache,  # noqa: E402
                          model_specs)
from repro.models.common import param_count  # noqa: E402
from repro.models.rwkv6 import CHUNK, _chunk_scan  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST
#: float32 rounding in a different order of summation, relative to the
#: largest logit (or state entry) compared
RTOL = 1e-4


def _mm(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _bench_config():
    return json.loads(
        (ROOT / "chipbench" / "configs" / "rwkv6-7b-1chip.json").read_text())


@pytest.fixture(scope="module")
def tiny():
    """The benchmark's configuration file at toy widths, float32, and
    weights drawn by the reference's laws."""
    cfg = _bench_config()
    cfg["arch"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                       head_dim=16, d_ff=128, vocab_size=256,
                       rwkv={"head_dim": 16, "decay_lora": 8, "mix_lora": 8},
                       param_dtype="float32", compute_dtype="float32",
                       use_pallas=False)
    arch = program.arch_config(cfg)
    return cfg, arch, program.draw_weights(cfg, arch, seed=11)


def _reference_logits(cfg, w, tokens):
    """The reference's full forward pass: logits at every position."""
    x = ref.embed(w, jnp.asarray(tokens))
    blk = ref.blocks(w)
    layer = jax.jit(lambda p, x: ref.layer(p, x, cfg, _mm))
    for li in range(cfg["arch"]["num_layers"]):
        x = layer(jax.tree.map(lambda a: a[li].astype(jnp.float32), blk), x)
    return np.asarray(_mm("sd,dv->sv", ref.final(w, x, cfg),
                          ref.unembed(w)))


def _assert_close(got, want):
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    scale = max(1.0, float(np.max(np.abs(want))))
    assert err < RTOL * scale, (err, scale)


@pytest.mark.parametrize("n_prompt", [2 * CHUNK, 40])
def test_engine_prefill_then_decode_matches_reference(tiny, n_prompt):
    """Two requests share the decode batch: one prompt of ``n_prompt``
    tokens (a multiple of the chunk, or the ``C = 1`` fallback) and one of
    33.  The prefill's logits and each cached decode step's logits of each
    row equal the reference's over that row's prompt and served tokens."""
    cfg, arch, w = tiny
    eng = ServingEngine(arch, params=w, batch_size=2, max_seq=96)
    decode, steps = eng._decode, []

    def logged(params, cache, tokens, pos):
        cache, logits = decode(params, cache, tokens, pos)
        steps.append((np.asarray(pos), np.asarray(logits)))
        return cache, logits

    eng._decode = logged
    rng = np.random.default_rng(n_prompt)
    reqs = [eng.submit(Request(f"r{i}", rng.integers(
        1, arch.vocab_size, n).astype(np.int32), max_new_tokens=6))
        for i, n in enumerate((n_prompt, 33))]
    with jax.default_matmul_precision("highest"):
        eng.drain()
        for slot, r in enumerate(reqs):
            n = len(r.prompt)
            _, first = eng._prefill(w, {"tokens": jnp.asarray(r.prompt[None])})
            got = [np.asarray(first[0])] + [
                lg[slot] for pos, lg in steps if pos[slot] >= n]
            assert len(got) == len(r.generated) == 6
            seq = np.concatenate([r.prompt, r.generated[:-1]])
            want = _reference_logits(cfg, w, seq)[n - 1:]
            _assert_close(np.stack(got), want)


@pytest.mark.parametrize("S", [3 * CHUNK, 40])
def test_chunk_scan_matches_per_token_recurrence(S):
    """The program's chunked scan, across chunk boundaries (or one token a
    chunk when S is not a multiple of the chunk), from a non-zero state,
    against the reference's recurrence token by token: every output and
    the final state."""
    B, H, hd = 2, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    r, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks[:3])
    # log-decays for decays between exp(-exp(1)) ~ 0.07 and ~0.999
    lw = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, hd), minval=-7.0,
                                     maxval=1.0))
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd))
    with jax.default_matmul_precision("highest"):
        y, s = _chunk_scan(r, k, v, lw, u, s0)
        for b in range(B):
            y_ref, s_ref = jax.jit(ref.wkv)(r[b], k[b], v[b], jnp.exp(lw[b]),
                                            u, s0[b])
            _assert_close(y[b], y_ref)
            _assert_close(s[b], s_ref)


@pytest.mark.parametrize("leaf", ["w_g", "ln0"])
def test_full_rank_gate_and_ln0_act(tiny, leaf):
    """The gate is one full (d, heads, head_dim) projection and ``ln0``
    normalises the embedding: perturbing either moves the program's
    logits, and the reference follows the program on the perturbed
    weights."""
    cfg, arch, w = tiny
    mixer = w["decoder"]["blocks"]["0"]["mixer"]
    assert mixer["w_g"].shape[1:] == (arch.d_model, arch.num_heads,
                                      arch.rwkv.head_dim)
    assert "w_g2" not in mixer
    if leaf == "w_g":
        moved = jax.tree.map(lambda a: a, w)
        g = moved["decoder"]["blocks"]["0"]["mixer"]["w_g"]
        moved["decoder"]["blocks"]["0"]["mixer"]["w_g"] = g + 0.5 * \
            jax.random.normal(jax.random.PRNGKey(1), g.shape) / np.sqrt(
                arch.d_model)
    else:
        moved = dict(w, ln0=dict(w["ln0"], scale=w["ln0"]["scale"] + 0.5))
    tokens = np.random.default_rng(3).integers(1, arch.vocab_size, 40)
    prefill = jax.jit(build_prefill_step(arch))
    with jax.default_matmul_precision("highest"):
        _, base = prefill(w, {"tokens": jnp.asarray(tokens[None])})
        _, got = prefill(moved, {"tokens": jnp.asarray(tokens[None])})
        want = _reference_logits(cfg, moved, tokens)[-1]
    assert float(jnp.max(jnp.abs(got - base))) > 1e-2
    _assert_close(got[0], want)


def test_reference_counts_match_program_shapes():
    """At the benchmark's published widths (shapes only, nothing drawn):
    the reference's parameter count is the program's, 221.8 M a block and
    4.086 B in all, and the state it prices a decode step by is the
    program's decode cache, 17.04 MB a row."""
    cfg = _bench_config()
    arch = program.arch_config(cfg)
    assert ref.param_count(cfg) == param_count(model_specs(arch)) \
        == 4_086_317_056
    rows, max_seq = cfg["engine"]["batch_size"], cfg["engine"]["max_seq"]
    cache = decode_cache(arch, rows, max_seq, abstract=True)
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(cache))
    assert held == rows * ref.state_bytes_per_row(cfg) == rows * 17_039_360
