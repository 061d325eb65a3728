"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles
(the tests pass interpret=True: the Pallas interpreter runs the kernel body
on the CPU; the compiled TPU path is checked by tests/test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import mha, mha_ref
from repro.kernels.rglru.ops import linear_recurrence, linear_recurrence_ref
from repro.kernels.rwkv6.ops import time_mix_scan, time_mix_ref

pytestmark = pytest.mark.slow    # heavy suite: excluded from make test-fast

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 512, 8, 1, 128),     # MQA
    (2, 192, 6, 3, 32),      # non-pow2 seq (padding path)
    (1, 128, 4, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, K, hd, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, K, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, K, hd)), dtype)
    out = mha(q, k, v, causal=causal, block_q=64, block_k=64,
              interpret=True)
    ref = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shape_invariance(block_q, block_k):
    q = jnp.asarray(RNG.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    out = mha(q, k, v, causal=True, block_q=block_q, block_k=block_k,
              interpret=True)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 64, 2, 32, 32),
    (2, 128, 4, 64, 32),
    (1, 256, 2, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_kernel_sweep(B, S, H, hd, chunk, dtype):
    r = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    # log-decay ≤ 0, including strong decay (the overflow-prone regime the
    # pairwise-exponent formulation is exact for)
    lw = -jnp.asarray(RNG.uniform(0.01, 4.0, size=(B, S, H, hd)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, hd)), jnp.float32)
    out = time_mix_scan(r, k, v, lw, u, chunk=chunk, interpret=True)
    ref = time_mix_ref(r, k, v, lw, u)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))) / scale
    assert err < (2e-2 if dtype == jnp.bfloat16 else 1e-5), err


@pytest.mark.parametrize("B,S,W,chunk,block_w", [
    (1, 128, 128, 32, 128),
    (2, 256, 256, 64, 128),
    (1, 512, 384, 128, 128),
])
def test_rglru_kernel_sweep(B, S, W, chunk, block_w):
    a = jnp.asarray(RNG.uniform(0.2, 0.999, size=(B, S, W)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, S, W)), jnp.float32)
    h = linear_recurrence(a, b, chunk=chunk, block_w=block_w,
                          interpret=True)
    ref = linear_recurrence_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_state_continuity():
    """Chunk boundaries must be invisible: one chunk == many chunks."""
    B, S, H, hd = 1, 128, 2, 32
    r = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    lw = -jnp.asarray(RNG.uniform(0.05, 1.0, size=(B, S, H, hd)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, hd)), jnp.float32)
    o32 = time_mix_scan(r, k, v, lw, u, chunk=32, interpret=True)
    o128 = time_mix_scan(r, k, v, lw, u, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o32), np.asarray(o128),
                               rtol=1e-4, atol=1e-4)
