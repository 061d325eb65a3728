"""The paged decode kernel (interpret=True: the Pallas interpreter runs its
body on the CPU) against the gather path it replaces on the chip,
``repro.models.attention.paged_gather_attention``, at the served model's
head shape: 8 KV heads of 128, 6 query heads each, bf16 pools of 16-token
pages.  The compiled TPU path is checked by tests/test_tpu_compile.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode import paged_decode
from repro.models.attention import paged_gather_attention

K, G, HD, PAGE, WIDTH, POOL = 8, 6, 128, 16, 8, 32


def _private_tables(lengths, rng):
    """Each row its own pages (distinct across rows), 0 past its last."""
    need = [-(-n // PAGE) for n in lengths]
    ids = rng.permutation(np.arange(1, POOL + 1))
    tables = np.zeros((len(lengths), WIDTH), np.int32)
    at = 0
    for r, n in enumerate(need):
        tables[r, :n] = ids[at:at + n]
        at += n
    return tables


def _case(name, rng):
    """-> (lengths, tables, layer): keys per row (pos + 1), page tables and
    the index into a stacked pool (None: one layer's unstacked pool)."""
    if name.startswith("len"):
        n = int(name[3:])
        lengths = [n, n, n]
        return lengths, _private_tables(lengths, rng), 1
    if name == "full_table":
        lengths = [WIDTH * PAGE, 40]
        return lengths, _private_tables(lengths, rng), 1
    if name == "unstacked":
        lengths = [33, 70]
        return lengths, _private_tables(lengths, rng), None
    if name == "layer0":
        lengths = [33, 70]
        return lengths, _private_tables(lengths, rng), 0
    if name == "shared_prefix":
        # rows 0 and 1 read the same first three pages, then their own
        lengths = [60, 55, 20]
        tables = _private_tables(lengths, rng)
        tables[1, :3] = tables[0, :3]
        return lengths, tables, 2
    if name == "dead_rows":
        # dead rows: pos 0 through the all-zero table (the null page)
        lengths = [1, 90, 1, 17, 1]
        tables = _private_tables(lengths, rng)
        tables[[0, 2, 4]] = 0
        return lengths, tables, 1
    if name == "mixed":
        lengths = [128, 1, 65, 64, 100, 3]
        return lengths, _private_tables(lengths, rng), 2
    raise ValueError(name)


@pytest.mark.parametrize("name", ["len1", "len15", "len16", "len17",
                                  "full_table", "unstacked", "layer0",
                                  "shared_prefix", "dead_rows", "mixed"])
def test_kernel_matches_gather_path(name):
    rng = np.random.default_rng(7)
    lengths, tables, layer = _case(name, rng)
    B = len(lengths)
    stack = (3,) if layer is not None else ()
    shape = stack + (POOL + 1, PAGE, K, HD)
    k_pool = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, 1, K * G, HD)), jnp.bfloat16)
    pos = jnp.asarray(lengths, jnp.int32) - 1
    tables = jnp.asarray(tables)
    # blocks of 4 pages: every row past 64 keys crosses a block boundary
    out = paged_decode(q[:, 0], k_pool, v_pool, pos + 1, tables,
                       0 if layer is None else layer, pages_per_block=4,
                       interpret=True)
    ref = paged_gather_attention(q, k_pool, v_pool, pos, tables,
                                 page_size=PAGE, layer=layer)[:, 0]
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_step_routes_through_the_kernel():
    """A paged decode step whose config sets ``use_pallas`` (bf16 pool,
    heads of 128) attends through the kernel, inside the layer scan, at the
    scan's layer index: the same logits as the gather path's."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.models import (build_decode_step_paged, decode_cache_paged,
                              model_specs)
    from repro.models.common import init_params
    from repro.serving.smoke import LOGIT_RTOL

    cfg0 = reduced(get_config("internlm2-20b"), num_layers=2, num_heads=12,
                   num_kv_heads=2, head_dim=HD, param_dtype="bfloat16",
                   compute_dtype="bfloat16")
    rng = np.random.default_rng(3)
    lengths = [1, 40, 17]
    B, pool = len(lengths), 3 * WIDTH
    tables = jnp.asarray(_private_tables(lengths, rng) % pool)
    cache = decode_cache_paged(cfg0, B, WIDTH * PAGE, pool, PAGE)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), cache)
    params = init_params(model_specs(cfg0), seed=4)
    tok = jnp.asarray(rng.integers(0, cfg0.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(lengths, jnp.int32) - 1
    logits = {}
    for pallas in (False, True):
        cfg = dataclasses.replace(cfg0, use_pallas=pallas)
        step = build_decode_step_paged(cfg, PAGE)
        jaxpr = jax.make_jaxpr(step)(params, cache, tok, pos, tables)
        assert ("pallas_call" in str(jaxpr)) == pallas
        _, logits[pallas] = jax.jit(step)(params, cache, tok, pos, tables)
    a, b = np.asarray(logits[True]), np.asarray(logits[False])
    assert np.linalg.norm(a - b) / np.linalg.norm(b) <= LOGIT_RTOL["bfloat16"]
