"""The cycle's slot-granular decode updates the stacked cache in place.

``Stack._decode_blocks`` carries the whole layer-stacked cache in the layer
scan and writes each layer's index back.  It must give every cache leaf and
the logits of the form that scans the stack as xs and re-stacks it as ys,
step after step: a leaf never written back, or written back in the wrong
dtype, shows here at once, not a step later as a drifting logit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_decode_step, decode_cache, model_specs
from repro.models.common import init_params
from repro.models.transformer import Stack, apply_layer_decode


def _scanned_xs_ys(self, bp, x, caches, pos, aux, tables, page_size):
    """Reference: each layer's cache scanned in as xs, its new cache
    stacked back as ys."""
    def body(carry, scanned):
        x, aux = carry
        lp, lc = scanned
        out = {}
        for i, d in enumerate(self.cycle):
            x, out[str(i)], aux = apply_layer_decode(
                self.cfg, d, lp[str(i)], x, lc[str(i)], pos, aux)
        return (x, aux), out

    (x, aux), new = jax.lax.scan(body, (x, aux), (bp, caches))
    return x, new, aux


@pytest.mark.parametrize("arch", ["rwkv6-7b", "recurrentgemma-9b",
                                  "internlm2-20b"])
def test_carried_decode_matches_scanned_xs_ys(arch, monkeypatch):
    cfg = reduced(get_config(arch))
    params = init_params(model_specs(cfg), seed=4)
    B, max_seq = 3, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))
    cache = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape).astype(a.dtype),
        decode_cache(cfg, B, max_seq, abstract=True))
    pos = jnp.asarray([5, 0, 11], jnp.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    step = jax.jit(build_decode_step(cfg)).lower(params, cache, tok,
                                                 pos).compile()
    with monkeypatch.context() as m:
        m.setattr(Stack, "_decode_blocks", _scanned_xs_ys)
        ref = jax.jit(build_decode_step(cfg)).lower(params, cache, tok,
                                                    pos).compile()
    rng = np.random.default_rng(2)
    got = want = cache
    for _ in range(2):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 1)), jnp.int32)
        got, logits = step(params, got, tok, pos)
        want, ref_logits = ref(params, want, tok, pos)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits), rtol=1e-6,
                                   atol=1e-6)
        assert jax.tree.structure(got) == jax.tree.structure(want)

        def same(a, b):
            assert a.dtype == b.dtype == jnp.float32, (a.dtype, b.dtype)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        jax.tree.map(same, got, want)
        pos = pos + 1
