"""Compile the main path's kernels and steps for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
compile against a described ``v5e:2x2`` topology and fail on what the
chip's compiler would refuse (tiling, unsupported lowerings, memory), which
interpret-mode tests cannot see.  Nothing runs.  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.internlm2_20b import ONE_CHIP
from repro.roofline.analysis import V5E

pytestmark = pytest.mark.slow    # heavy suite: excluded from make test-fast

BATCH, MAX_SEQ, PAGE = 16, 2048, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """This process sees the CPU backend; steer the model path to the
    compiled kernels the chip would run."""
    import repro.kernels

    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)


def _sd(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _abstract(tree, one_chip):
    return jax.tree.map(lambda s: _sd(one_chip, s.shape, s.dtype), tree)


def _params(cfg, one_chip):
    from repro.models import model_specs
    from repro.models.common import abstract_params

    return _abstract(abstract_params(model_specs(cfg)), one_chip)


@pytest.mark.parametrize("S", [512, 9])
def test_flash_kernel_compiles_at_internlm2_widths(one_chip, S):
    from repro.kernels.flash_attention.ops import mha

    q = _sd(one_chip, (1, S, 48, 128), jnp.bfloat16)
    kv = _sd(one_chip, (1, S, 8, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: mha(q, k, v, causal=True,
                                           interpret=False)
                       ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rwkv6_scan_compiles_at_rwkv6_7b_widths(one_chip):
    from repro.kernels.rwkv6.ops import time_mix_scan

    shape = (1, 256, 64, 64)           # (B, S, heads, head_dim 64)
    x = _sd(one_chip, shape, jnp.bfloat16)
    lw = _sd(one_chip, shape, jnp.float32)
    u = _sd(one_chip, (64, 64), jnp.float32)
    compiled = jax.jit(lambda r, k, v, lw, u: time_mix_scan(
        r, k, v, lw, u, chunk=32, interpret=False)).lower(
        x, x, x, lw, u).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip):
    from repro.kernels.rglru.ops import linear_recurrence

    x = _sd(one_chip, (1, 512, 4096), jnp.float32)   # lru_width 4096
    compiled = jax.jit(lambda a, b: linear_recurrence(
        a, b, chunk=64, block_w=128, interpret=False)).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _assert_fits(compiled):
    ma = compiled.memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert need <= V5E.hbm_bytes, (ma.argument_size_in_bytes,
                                   ma.temp_size_in_bytes)


@pytest.mark.parametrize("paged", [False, True], ids=["slot", "paged"])
def test_one_chip_decode_step_compiles_and_fits(one_chip, paged):
    from repro.models import (build_decode_step, build_decode_step_paged,
                              decode_cache, decode_cache_paged)

    cfg = ONE_CHIP
    params = _params(cfg, one_chip)
    tok = _sd(one_chip, (BATCH, 1), jnp.int32)
    pos = _sd(one_chip, (BATCH,), jnp.int32)
    if paged:
        pages = BATCH * MAX_SEQ // PAGE
        cache = _abstract(decode_cache_paged(cfg, BATCH, MAX_SEQ, pages,
                                             PAGE, abstract=True), one_chip)
        tables = _sd(one_chip, (BATCH, MAX_SEQ // PAGE), jnp.int32)
        step = jax.jit(build_decode_step_paged(cfg, PAGE), donate_argnums=1)
        compiled = step.lower(params, cache, tok, pos, tables).compile()
    else:
        cache = _abstract(decode_cache(cfg, BATCH, MAX_SEQ, abstract=True),
                          one_chip)
        step = jax.jit(build_decode_step(cfg), donate_argnums=1)
        compiled = step.lower(params, cache, tok, pos).compile()
    _assert_fits(compiled)


def test_one_chip_prefill_runs_the_flash_kernel(one_chip, compiled_kernels):
    from repro.models import build_prefill_step

    cfg = dataclasses.replace(ONE_CHIP, use_pallas=True)
    params = _params(cfg, one_chip)
    batch = {"tokens": _sd(one_chip, (1, 512), jnp.int32)}
    compiled = jax.jit(build_prefill_step(cfg)).lower(params, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)
