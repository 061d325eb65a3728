"""Serving-path tests: engine correctness, continuous batching, admission.

Covers an idle drain, a lone request in a wide batch, token-metric
exactness, the structured refusals of malformed requests, parity of the
continuous-batching loop (slot reuse, per-row timelines) with a plain
greedy decode of each request alone, the KV layout decision, and the LM
serving adapter's structured refusals.
"""
import threading

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.errors import AdmissionRefused, ErrorCode
from repro.serving import Request, ServingEngine
from repro.serving.engine import kv_pool_pages
from tests.greedy_reference import greedy_reference

ARCH = "internlm2-20b"
MAX_SEQ = 64


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config(ARCH))


@pytest.fixture(scope="module")
def params(cfg):
    from repro.models import model_specs
    from repro.models.common import init_params

    return init_params(model_specs(cfg), seed=1)


def make_engine(cfg, params, batch_size=4, max_seq=MAX_SEQ):
    return ServingEngine(cfg, params=params, batch_size=batch_size,
                         max_seq=max_seq)


def make_prompt(rng, cfg, n):
    return rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)


# -- submit / drain edge cases -----------------------------------------------

def test_drain_with_nothing_submitted_emits_nothing(cfg, params):
    eng = make_engine(cfg, params)
    eng.drain()
    assert eng.metrics["tokens"] == 0
    assert eng.metrics["decode_steps"] == 0


def test_single_request_in_wide_batch_done_exact(cfg, params):
    """One request in a 4-row engine: done flips at exactly max_new_tokens
    (never an over-append), N tokens take N-1 decode steps (the first comes
    from the prime), and only the live row's tokens are counted."""
    rng = np.random.default_rng(0)
    eng = make_engine(cfg, params, batch_size=4)
    r = eng.submit(Request("a", make_prompt(rng, cfg, 6), max_new_tokens=5))
    eng.drain()
    assert r.done and len(r.generated) == 5
    assert eng.metrics["decode_steps"] == 4
    assert eng.metrics["tokens"] == 5


@pytest.mark.parametrize("prompt_len,max_new,message", [
    (40, 1, "prompt length 40 exceeds max_seq 32"),
    (0, 1, "empty prompt"),
    (30, 10, "kv cache overflow: prompt 30 + max_new_tokens 10 exceeds "
             "max_seq 32"),
    (4, 0, "bad request: max_new_tokens 0 < 1"),
], ids=["over_max_seq", "empty", "cache_overflow", "no_new_tokens"])
def test_submit_refuses_malformed_request(cfg, params, prompt_len, max_new,
                                          message):
    eng = make_engine(cfg, params, batch_size=2, max_seq=32)
    with pytest.raises(AdmissionRefused) as ei:
        eng.submit(Request("bad", np.ones(prompt_len, np.int32),
                           max_new_tokens=max_new))
    assert ei.value.code == ErrorCode.BAD_REQUEST
    assert message in str(ei.value)
    assert eng.backlog_tokens() == 0        # refusal touches no engine state


# -- continuous batching ------------------------------------------------------

def test_continuous_matches_single_runs_mixed_lengths(cfg, params):
    """The tentpole exactness claim: requests of different prompt lengths
    and budgets flowing through the shared decode batch (joining, leaving,
    slot reuse) produce token-for-token the same output as isolated runs."""
    rng = np.random.default_rng(4)
    eng = make_engine(cfg, params, batch_size=2)
    shapes = [(5, 3), (9, 6), (6, 1), (7, 4), (8, 5)]   # > 2x slots: reuse
    reqs = [Request(f"r{i}", make_prompt(rng, cfg, pl), max_new_tokens=mn)
            for i, (pl, mn) in enumerate(shapes)]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    assert all(r.done and len(r.generated) == r.max_new_tokens for r in reqs)
    assert eng.metrics["tokens"] == sum(mn for _, mn in shapes)
    for r in reqs:
        assert r.generated == greedy_reference(
            cfg, params, r.prompt, r.max_new_tokens, MAX_SEQ), r.request_id


def test_continuous_telemetry_stamps(cfg, params):
    rng = np.random.default_rng(5)
    eng = make_engine(cfg, params, batch_size=2)
    r = eng.submit(Request("t", make_prompt(rng, cfg, 6), max_new_tokens=4))
    eng.drain()
    assert r.ttft_ms is not None and r.ttft_ms >= 0.0
    assert r.tokens_per_s is not None and r.tokens_per_s > 0.0
    assert not r.expired


def test_continuous_submit_threadsafe_with_driver(cfg, params):
    rng = np.random.default_rng(6)
    eng = make_engine(cfg, params, batch_size=2)
    stop = threading.Event()
    driver = threading.Thread(target=eng.serve_forever, args=(stop,),
                              daemon=True)
    driver.start()
    done = threading.Event()
    finished = []
    eng.on_complete = lambda r: (finished.append(r),
                                 done.set() if len(finished) == 6 else None)
    reqs = [eng.submit(Request(f"p{i}", make_prompt(rng, cfg, 6),
                               max_new_tokens=3)) for i in range(6)]
    assert done.wait(60.0), "driver thread did not finish the queue"
    stop.set()
    eng.wake()          # the idle park is unbounded, not a poll
    driver.join(timeout=5.0)
    assert not driver.is_alive(), "driver did not observe stop after wake"
    assert all(r.done and len(r.generated) == 3 for r in reqs)


def test_continuous_admission_hook_refuses(cfg, params):
    eng = make_engine(cfg, params)

    def refuse(r, engine):
        raise AdmissionRefused(ErrorCode.DEADLINE,
                               f"{r.request_id}: over deadline budget")

    eng.admission = refuse
    with pytest.raises(AdmissionRefused) as ei:
        eng.submit(Request("no", np.ones(4, np.int32)))
    assert ei.value.code == ErrorCode.DEADLINE
    assert eng.backlog_tokens() == 0        # refusal touches no engine state


@pytest.mark.slow
def test_continuous_parity_ring_buffer_and_recurrent_state():
    """Hard case: per-row timelines over ring-buffered local attention and
    recurrent state (recurrentgemma mixes both)."""
    from repro.models import model_specs
    from repro.models.common import init_params

    cfg = reduced(get_config("recurrentgemma-9b"))
    params = init_params(model_specs(cfg), seed=2)
    rng = np.random.default_rng(7)
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=MAX_SEQ)
    shapes = [(6, 4), (9, 7), (5, 3)]
    reqs = [Request(f"r{i}", make_prompt(rng, cfg, pl), max_new_tokens=mn)
            for i, (pl, mn) in enumerate(shapes)]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    for r in reqs:
        assert r.generated == greedy_reference(
            cfg, params, r.prompt, r.max_new_tokens, MAX_SEQ), r.request_id


# -- kv layout ----------------------------------------------------------------

@pytest.mark.parametrize("arch,paged,pool_pages,want", [
    (ARCH, False, None, 0),
    ("rwkv6-7b", True, None, 0),            # no cache leaf can page
    (ARCH, True, None, 4 * 5),              # 4 rows x ceil(70 / 16) pages
    (ARCH, True, 48, 48),
], ids=["paging_off", "nothing_pageable", "rows_x_max_pages", "explicit"])
def test_kv_pool_pages(arch, paged, pool_pages, want):
    cfg = reduced(get_config(arch))
    assert kv_pool_pages(cfg, paged, batch_size=4, max_seq=70, page_size=16,
                         pool_pages=pool_pages) == want


# -- control-plane adapter ----------------------------------------------------

@pytest.fixture(scope="module")
def serving_orchestrator():
    from repro.core.orchestrator import Orchestrator
    from repro.substrates import LmServingAdapter

    orch = Orchestrator(plane="serving-test")
    adapter = LmServingAdapter(reduced(get_config(ARCH)), batch_size=2,
                               max_seq=MAX_SEQ)
    orch.register(adapter)
    yield orch, adapter
    adapter.close()


def _task(task_id, prompt_len=6, max_new=4, budget_ms=None):
    from repro.core.tasks import TaskRequest

    return TaskRequest(
        task_id=task_id, function="generate",
        input_modality="tokens", output_modality="tokens",
        payload={"prompt": list(range(1, prompt_len + 1)),
                 "max_new_tokens": max_new},
        latency_budget_ms=budget_ms)


def test_adapter_serves_with_telemetry(serving_orchestrator):
    orch, adapter = serving_orchestrator
    res, trace = orch.execute(_task("ok-1"))
    assert res.status == "completed"
    assert trace.selected == adapter.resource_id
    assert len(res.output["tokens"]) == 4
    for field in ("ttft_ms", "tokens_per_s", "step_ms", "drift_score"):
        assert field in res.telemetry
    assert res.telemetry["deadline_expired"] is False


def test_adapter_refuses_doomed_deadline_as_structured_DEADLINE(
        serving_orchestrator):
    orch, adapter = serving_orchestrator
    res, trace = orch.execute(_task("doom-1", max_new=40, budget_ms=0.2))
    assert res.status == "rejected"
    assert res.error_code == ErrorCode.DEADLINE.value
    assert "deadline budget" in trace.rejected_reason
    # a refusal is admission control, not substrate failure: the breaker
    # must stay closed and the next request must serve normally
    res2, _ = orch.execute(_task("ok-2"))
    assert res2.status == "completed"


def test_adapter_rejects_overlong_prompt_as_BAD_REQUEST(serving_orchestrator):
    orch, _ = serving_orchestrator
    res, _ = orch.execute(_task("long-1", prompt_len=MAX_SEQ + 10))
    assert res.status == "rejected"
    assert res.error_code == ErrorCode.BAD_REQUEST.value


def test_adapter_descriptor_and_twin(serving_orchestrator):
    orch, adapter = serving_orchestrator
    desc = adapter.descriptor()
    assert "generate" in desc.capability.functions
    assert desc.capability.input_signal.modality == "tokens"
    twin = orch.twins.get(adapter.resource_id)
    assert twin is not None and twin.surrogate is not None
    sim = twin.surrogate.simulate(_task("sim-1"))
    assert sim["output"]["predicted"] is True
    assert sim["telemetry"]["step_ms"] > 0.0


@pytest.mark.parametrize("arch,paged", [(ARCH, True), (ARCH, False),
                                        ("rwkv6-7b", True)],
                         ids=["paged", "slot", "nothing_pageable"])
def test_adapter_prices_the_pool_its_engine_holds(arch, paged):
    from repro.substrates import LmServingAdapter

    adapter = LmServingAdapter(reduced(get_config(arch)), batch_size=2,
                               max_seq=MAX_SEQ, paged=paged, page_size=8,
                               calibrate=False)
    adapter.prepare(None)
    try:
        pages = adapter.engine.pool_pages
        assert (adapter.cost.pool_pages or 0) == adapter.pool_pages == pages
        assert adapter.engine.pool_stats().get("pool_pages", 0) == pages
        assert ("paged kv pool=" in adapter.descriptor().description) \
            == (pages > 0)
    finally:
        adapter.close()
    assert pages == (2 * MAX_SEQ // 8 if arch == ARCH and paged else 0)
