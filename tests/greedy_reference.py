"""Plain greedy decode of one prompt: the reference the serving tests hold
the engine to.  Batch 1, a contiguous cache, one scalar position a step:
it shares the model's prefill and decode steps with the engine and nothing
of its primes, slots, pages or emit."""
import jax
import jax.numpy as jnp

from repro.models import build_decode_step, build_prefill_step, decode_cache
from repro.serving.cache_utils import extend_cache


def greedy_reference(cfg, params, prompt, max_new_tokens, max_seq):
    """The ``max_new_tokens`` greedy tokens that follow ``prompt``."""
    S = len(prompt)
    pcache, logits = jax.jit(build_prefill_step(cfg))(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None, :]})
    cache = extend_cache(decode_cache(cfg, 1, max_seq), pcache, S)
    decode = jax.jit(build_decode_step(cfg))
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    out = [int(token[0, 0])]
    for step in range(max_new_tokens - 1):
        cache, logits = decode(params, cache, token, jnp.int32(S + step))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out.append(int(token[0, 0]))
    return out
