"""Compile the main path's kernels and steps for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
compile against a described ``v5e:2x2`` topology and fail on what the
chip's compiler would refuse (tiling, unsupported lowerings, memory), which
interpret-mode tests cannot see.  Nothing runs.  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import dataclasses
import math
import re

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


def _compile_decode_step(one_chip, paged, cfg=ONE_CHIP):
    from repro.models import (build_decode_step, build_decode_step_paged,
                              decode_cache, decode_cache_paged)

    params = _params(cfg, one_chip)
    tok = _sd(one_chip, (BATCH, 1), jnp.int32)
    pos = _sd(one_chip, (BATCH,), jnp.int32)
    if paged:
        pages = BATCH * MAX_SEQ // PAGE
        cache = _abstract(decode_cache_paged(cfg, BATCH, MAX_SEQ, pages,
                                             PAGE, abstract=True), one_chip)
        tables = _sd(one_chip, (BATCH, MAX_SEQ // PAGE), jnp.int32)
        step = jax.jit(build_decode_step_paged(cfg, PAGE), donate_argnums=1)
        return step.lower(params, cache, tok, pos, tables).compile(), cache
    cache = _abstract(decode_cache(cfg, BATCH, MAX_SEQ, abstract=True),
                      one_chip)
    step = jax.jit(build_decode_step(cfg), donate_argnums=1)
    return step.lower(params, cache, tok, pos).compile(), cache


@pytest.mark.parametrize("paged", [False, True], ids=["slot", "paged"])
def test_one_chip_decode_step_compiles_and_fits(one_chip, compiled_kernels,
                                                paged):
    compiled, _ = _compile_decode_step(one_chip, paged)
    _assert_fits(compiled)


def test_one_chip_paged_decode_runs_the_paged_kernel(one_chip,
                                                     compiled_kernels):
    """The paged step attends through the paged decode kernel, which reads
    each row's pages from the stacked pool in place: no (B, T, K, hd) copy
    of a layer's tables is gathered, and the step's temporaries stay below
    the gather path's and below one such copy."""
    compiled, cache = _compile_decode_step(one_chip, paged=True)
    text = compiled.as_text()
    assert re.search(r"%paged_decode\S* = \S+ custom-call\(", text)
    K, hd = cache["blocks"]["0"]["k"].shape[-2:]
    copy_shapes = {f"bf16[{BATCH},{MAX_SEQ},{K},{hd}]",
                   f"bf16[{BATCH * MAX_SEQ // PAGE},{PAGE},{K},{hd}]"}
    gathers = [m.group(0) for m in re.finditer(r"= (bf16\[[\d,]*\])\S* \w+",
                                               text)
               if m.group(1) in copy_shapes]
    assert not gathers, gathers[:4]
    temp = compiled.memory_analysis().temp_size_in_bytes
    gather_twin, _ = _compile_decode_step(
        one_chip, paged=True, cfg=dataclasses.replace(ONE_CHIP,
                                                      use_pallas=False))
    assert temp <= gather_twin.memory_analysis().temp_size_in_bytes
    assert temp < BATCH * MAX_SEQ * K * hd * 2, temp


@pytest.fixture(scope="module")
def rwkv6_decode(one_chip):
    """The benchmark's RWKV-6 configuration (published widths, 16 of 32
    layers) at its 64 rows of resident state, slot-granular: the compiled
    decode step and its abstract cache."""
    import json
    import sys
    from pathlib import Path

    from repro.models import build_decode_step, decode_cache

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from chipbench.harness import program

    cfgj = json.loads(
        (root / "chipbench" / "configs" / "rwkv6-7b-1chip.json").read_text())
    cfg = program.arch_config(cfgj)
    rows, max_seq = cfgj["engine"]["batch_size"], cfgj["engine"]["max_seq"]
    cache = _abstract(decode_cache(cfg, rows, max_seq, abstract=True),
                      one_chip)
    step = jax.jit(build_decode_step(cfg), donate_argnums=1)
    compiled = step.lower(_params(cfg, one_chip), cache,
                          _sd(one_chip, (rows, 1), jnp.int32),
                          _sd(one_chip, (rows,), jnp.int32)).compile()
    return compiled, cache


def test_rwkv6_one_chip_decode_step_compiles_and_fits(rwkv6_decode):
    _assert_fits(rwkv6_decode[0])


def test_rwkv6_one_chip_decode_step_updates_the_state_in_place(rwkv6_decode):
    """The stacked WKV state rides in the layer scan's carry, and each layer
    reads and writes its own index of it in place: no buffer of the stack's
    shape is allocated or copied into the output, and no layer's state is
    copied out of it either."""
    compiled, cache = rwkv6_decode
    s = cache["blocks"]["0"]["s"]                 # (layers, rows, H, hd, hd)
    assert s.dtype == jnp.float32
    shape = f"f32[{','.join(map(str, s.shape))}]"
    moves = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if (m := re.search(r"= (\w+\[[\d,]*\])\S* (copy|custom-call)\(",
                                line))
             and m.group(1) == shape
             and (m.group(2) == "copy" or "AllocateBuffer" in line)]
    assert not moves, moves
    layer_bytes = math.prod(s.shape[1:]) * s.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)


def test_one_chip_paged_decode_updates_the_pool_in_place(one_chip,
                                                        compiled_kernels):
    """The stacked KV pool rides in the layer scan's carry: each step
    scatters its new rows into it and reads it through the tables, and never
    copies, slices out or writes back a layer's pool or the whole stack."""
    compiled, cache = _compile_decode_step(one_chip, paged=True)
    stack = cache["blocks"]["0"]["k"].shape       # (layers, pages+1, ...)
    pool_elems = {math.prod(stack), math.prod(stack[1:])}
    moves = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]*)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(",
        compiled.as_text())
        if math.prod(int(d) for d in m.group(1).split(",") if d) in pool_elems]
    assert not moves, moves
    leaf_bytes = math.prod(stack) * cache["blocks"]["0"]["k"].dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf_bytes, (temp, leaf_bytes)


def test_one_chip_prefill_runs_the_flash_kernel(one_chip, compiled_kernels):
    from repro.models import build_prefill_step

    cfg = dataclasses.replace(ONE_CHIP, use_pallas=True)
    params = _params(cfg, one_chip)
    batch = {"tokens": _sd(one_chip, (1, 512), jnp.int32)}
    compiled = jax.jit(build_prefill_step(cfg)).lower(params, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)
