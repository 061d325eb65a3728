"""Paged-KV serving tests: parity, capacity, saturation, clock injection.

The paged path must be *token-for-token identical* to the slot-granular
path — paging changes where KV bytes live, never what attention reads.
Parity runs across the cache families (pure global attention, MLA latents,
and the hybrid ring-buffer stack that degrades to slot-granular), then the
capacity properties: a request longer than the old per-slot cap completes
under the same HBM budget, pool exhaustion refuses with a structured
``QUEUE_SATURATED`` + ``retry_after_s``, and a drained engine holds zero
leaked pages.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.errors import AdmissionRefused, ErrorCode
from repro.core.simclock import VirtualClock
from repro.models import (build_decode_step_paged, decode_cache_paged,
                          model_specs)
from repro.models.common import init_params
from repro.models.transformer import Stack, apply_layer_decode
from repro.roofline.serving import ServingCostModel
from repro.serving import Request, ServingEngine
from tests.greedy_reference import greedy_reference

#: one arch per cache family: pure global-attention KV, MLA latent KV, and
#: a recurrent/ring hybrid with no pageable leaves at all
FAMILIES = ["internlm2-20b", "deepseek-v2-236b", "recurrentgemma-9b"]


@pytest.fixture(scope="module", params=FAMILIES)
def fam(request):
    cfg = reduced(get_config(request.param))
    return request.param, cfg, init_params(model_specs(cfg), seed=1)


@pytest.fixture(scope="module")
def attn():
    cfg = reduced(get_config("internlm2-20b"))
    return cfg, init_params(model_specs(cfg), seed=1)


def make_prompt(rng, cfg, n):
    return rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)


def run_trace(eng, prompts, max_new):
    reqs = [eng.submit(Request(f"r{i}", p, max_new_tokens=mn))
            for i, (p, mn) in enumerate(zip(prompts, max_new))]
    eng.drain()
    return [r.generated for r in reqs]


# -- parity -------------------------------------------------------------------

def test_paged_parity_token_for_token(fam):
    arch, cfg, params = fam
    rng = np.random.default_rng(11)
    # mixed lengths + one long-decode request that grows across several
    # page boundaries mid-flight
    prompts = [make_prompt(rng, cfg, n) for n in (5, 12, 9, 17, 3)]
    max_new = [6, 6, 6, 6, 21]
    base = ServingEngine(cfg, params=params, batch_size=3, max_seq=64)
    paged = ServingEngine(cfg, params=params, batch_size=3, max_seq=64,
                          paged=True, page_size=8, pool_pages=48)
    a = run_trace(base, prompts, max_new)
    b = run_trace(paged, prompts, max_new)
    assert a == b, f"{arch}: paged decode diverged from contiguous"
    if arch == "recurrentgemma-9b":
        # no pageable leaves: paged mode degrades to slot-granular
        assert paged.pool_stats() == {}
    else:
        assert paged.pool_stats()["pool_pages"] == 48


def _slice_scatter_gather(self, bp, x, caches, pos, aux, tables, page_size):
    """Reference for the cycle's paged decode: each layer's pool sliced out
    of the stack, its new row scattered and the tables gathered there, and
    the layers' pools stacked back."""
    def body(carry, scanned):
        x, aux = carry
        lp, lc = scanned
        out = {}
        for i, d in enumerate(self.cycle):
            x, out[str(i)], aux = apply_layer_decode(
                self.cfg, d, lp[str(i)], x, lc[str(i)], pos, aux,
                tables=tables, page_size=page_size)
        return (x, aux), out

    (x, aux), new = jax.lax.scan(body, (x, aux), (bp, caches))
    return x, new, aux


@pytest.mark.parametrize("arch", ["internlm2-20b", "deepseek-v2-236b"])
def test_paged_decode_in_place_matches_per_layer_reference(arch, monkeypatch):
    """The decode step that updates the stacked pool in place gives the
    same logits and pool, bit for bit, as slicing each layer's pool out:
    over steps that cross page boundaries, rows at different positions,
    and a dead row that writes into null page 0."""
    cfg = reduced(get_config(arch))
    params = init_params(model_specs(cfg), seed=3)
    page, pages = 4, 20
    tables = jnp.asarray([[1, 2, 3, 0, 0, 0],       # 6..9: page 2 -> 3
                          [4, 5, 6, 7, 10, 0],      # 13..16: page 7 -> 10
                          [8, 9, 0, 0, 0, 0],       # 2..5: page 8 -> 9
                          [0, 0, 0, 0, 0, 0]],      # dead: null page 0
                         jnp.int32)
    pos = np.array([6, 13, 2, 0], np.int32)
    live = np.array([1, 1, 1, 0], np.int32)
    B, max_seq = tables.shape[0], tables.shape[1] * page
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    cache = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape).astype(a.dtype),
        decode_cache_paged(cfg, B, max_seq, pages, page, abstract=True))
    tok = jnp.zeros((B, 1), jnp.int32)
    args = (params, cache, tok, jnp.asarray(pos), tables)
    step = jax.jit(build_decode_step_paged(cfg, page)).lower(*args).compile()
    with monkeypatch.context() as m:
        m.setattr(Stack, "_decode_blocks", _slice_scatter_gather)
        ref = jax.jit(build_decode_step_paged(cfg, page)).lower(*args).compile()
    rng = np.random.default_rng(7)
    got = want = cache
    for _ in range(4):
        tok = jnp.asarray(make_prompt(rng, cfg, B)[:, None])
        got, logits = step(params, got, tok, jnp.asarray(pos), tables)
        want, ref_logits = ref(params, want, tok, jnp.asarray(pos), tables)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), got, want)
        pos = pos + live
    # the dead row wrote only null page 0; pages no table names are intact
    untouched = np.arange(11, pages + 1)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a)[:, untouched], np.asarray(b)[:, untouched]),
        got["blocks"], cache["blocks"])


def test_prefix_reuse_parity_and_suffix_only_prefill(attn):
    cfg, params = attn
    rng = np.random.default_rng(12)
    common = make_prompt(rng, cfg, 24)
    prompts = [np.concatenate([common, make_prompt(rng, cfg, 4 + i)])
               for i in range(4)]
    max_new = [5] * len(prompts)
    base = ServingEngine(cfg, params=params, batch_size=2, max_seq=64)
    paged = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                          paged=True, page_size=8, pool_pages=64)
    prefilled = []
    paged.on_prefill_ms = lambda tokens, ms: prefilled.append(tokens)
    a = run_trace(base, prompts, max_new)
    b = run_trace(paged, prompts, max_new)
    assert a == b, "prefix-shared decode diverged from contiguous"
    # first request prefills everything; the sharers only their suffix
    assert prefilled[0] == len(prompts[0])
    assert all(t <= len(p) - 24 for t, p in zip(prefilled[1:], prompts[1:]))
    stats = paged.pool_stats()
    assert stats["prefix_hit_rate"] > 0.5
    assert paged.cached_prefix_tokens(prompts[0]) >= 24


# -- capacity -----------------------------------------------------------------

def test_request_longer_than_slot_granular_cap_completes(attn):
    """Same KV HBM budget (64 cacheable tokens), opposite capacity shape:
    the slot-granular engine caps every request at 32 tokens; the paged
    engine serves one 49-token request by giving it 7 of the 8 pages."""
    cfg, params = attn
    rng = np.random.default_rng(13)
    prompt = make_prompt(rng, cfg, 40)
    old = ServingEngine(cfg, params=params, batch_size=2, max_seq=32)
    with pytest.raises(AdmissionRefused) as ei:
        old.submit(Request("long", prompt, max_new_tokens=9))
    assert ei.value.code == ErrorCode.BAD_REQUEST
    paged = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                          paged=True, page_size=8, pool_pages=8,
                          prefix_sharing=False)
    r = paged.submit(Request("long", prompt, max_new_tokens=9))
    paged.drain()
    assert r.done and len(r.generated) == 9
    # reference: the same request greedily decoded over a contiguous cache
    assert r.generated == greedy_reference(cfg, params, prompt, 9, 64)
    assert paged.audit_pages()["used"] == 0


def test_pool_exhaustion_refuses_queue_saturated(attn):
    cfg, params = attn
    rng = np.random.default_rng(14)
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                        paged=True, page_size=8, pool_pages=8,
                        prefix_sharing=False)
    held = [eng.submit(Request(f"h{i}", make_prompt(rng, cfg, 20),
                               max_new_tokens=12)) for i in range(2)]
    backlog_before = eng.backlog_tokens()
    with pytest.raises(AdmissionRefused) as ei:
        eng.submit(Request("over", make_prompt(rng, cfg, 20),
                           max_new_tokens=12))
    e = ei.value
    assert e.code == ErrorCode.QUEUE_SATURATED
    assert "queue saturated" in e.message
    assert e.detail["retry_after_s"] > 0
    assert e.detail["needed_pages"] == 4
    assert e.detail["pool_pages"] == 8
    # the refusal touched no engine state
    assert eng.backlog_tokens() == backlog_before
    eng.drain()
    assert all(r.done for r in held)
    # capacity freed: the refused request now admits and completes
    r = eng.submit(Request("retry", make_prompt(rng, cfg, 20),
                           max_new_tokens=12))
    eng.drain()
    assert r.done and len(r.generated) == 12
    assert eng.audit_pages() == {"pool_pages": 8, "used": 0, "free": 8,
                                 "reserved": 0}


def test_no_page_leaks_after_drain_and_flush(attn):
    cfg, params = attn
    rng = np.random.default_rng(15)
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                        paged=True, page_size=8, pool_pages=64)
    prompts = [make_prompt(rng, cfg, n) for n in (5, 12, 9)]
    run_trace(eng, prompts, [4, 4, 4])
    # after drain the only live pages are prefix-cache references
    stats = eng.audit_pages()
    assert stats["reserved"] == 0
    assert stats["used"] == eng.pool_stats()["pool_pages_used"]
    eng.flush()
    assert eng.audit_pages()["used"] == 0


def test_flush_releases_reservations_of_queued_work(attn):
    cfg, params = attn
    rng = np.random.default_rng(16)
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                        paged=True, page_size=8, pool_pages=8,
                        prefix_sharing=False)
    for i in range(2):
        eng.submit(Request(f"q{i}", make_prompt(rng, cfg, 20),
                           max_new_tokens=12))
    assert eng.audit_pages()["reserved"] == 8
    eng.flush()
    assert eng.audit_pages() == {"pool_pages": 8, "used": 0, "free": 8,
                                 "reserved": 0}
    assert eng.backlog_tokens() == 0


# -- backlog split ------------------------------------------------------------

def test_backlog_counts_unprefilled_prompt_tokens(attn):
    cfg, params = attn
    rng = np.random.default_rng(17)
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64)
    eng.submit(Request("a", make_prompt(rng, cfg, 10), max_new_tokens=4))
    eng.submit(Request("b", make_prompt(rng, cfg, 7), max_new_tokens=3))
    b = eng.backlog()
    assert b["prefill_tokens"] == 17
    assert b["decode_tokens"] == 7
    assert eng.backlog_tokens() == 24
    eng.drain()
    assert eng.backlog_tokens() == 0


def test_cost_model_prices_prefill_backlog_and_prefix_hits():
    cfg = reduced(get_config("internlm2-20b"))
    cost = ServingCostModel(cfg, batch_size=2, max_seq=64,
                            page_size=8, pool_pages=16)
    base = cost.predict_request_ms(32, 8)
    with_backlog = cost.predict_request_ms(32, 8, backlog_prefill_tokens=64)
    with_prefix = cost.predict_request_ms(32, 8, cached_prefix_tokens=24)
    assert with_backlog > base
    assert with_prefix < base
    assert cost.bytes_per_page > 0
    assert cost.page_hbm_bytes(4) == (cost.resident_cache_bytes
                                      + 4 * cost.bytes_per_page)
    assert cost.page_hbm_bytes(4, 2) > cost.page_hbm_bytes(4)


# -- clock seam ---------------------------------------------------------------

def test_engine_stamps_requests_on_injected_clock(attn):
    cfg, params = attn
    clk = VirtualClock()
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                        clock=clk)
    rng = np.random.default_rng(18)
    r = Request("v", make_prompt(rng, cfg, 6), max_new_tokens=3)
    eng.submit(r)
    clk.advance(1.5)                        # queue wait, in virtual time
    eng.drain()
    assert r.arrived_s == 0.0
    assert r.first_token_s == pytest.approx(1.5)
    assert r.ttft_ms == pytest.approx(1500.0)


def test_serve_forever_parks_unbounded_and_wakes_on_stop(attn):
    """The idle driver must not poll: with no work it parks on the engine
    condition until ``wake`` — and observes a stop immediately after."""
    cfg, params = attn
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64)
    stop = threading.Event()
    driver = threading.Thread(target=eng.serve_forever, args=(stop,),
                              daemon=True)
    driver.start()
    # park is unbounded (idle_wait_s=None): the thread stays alive, blocked
    driver.join(timeout=0.2)
    assert driver.is_alive()
    stop.set()
    eng.wake()
    driver.join(timeout=2.0)
    assert not driver.is_alive(), "driver did not wake on stop"
