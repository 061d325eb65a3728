"""Serving-path tests: engine correctness, continuous batching, admission.

Covers the serving satellite set: empty/partial batches, mixed prompt
lengths and max_new_tokens, token-metric exactness, batched-vs-single
greedy-decode parity — plus the continuous-batching loop (slot reuse,
per-row timelines) and the LM serving adapter's structured refusals.
"""
import threading

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.errors import AdmissionRefused, ErrorCode
from repro.serving import Request, ServingEngine

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


# -- fixed-batch generate() ---------------------------------------------------

def test_generate_empty_group_returns_empty(cfg, params):
    eng = make_engine(cfg, params)
    assert eng.generate([]) == []
    assert eng.metrics["tokens"] == 0


def test_generate_partial_batch(cfg, params):
    rng = np.random.default_rng(0)
    eng = make_engine(cfg, params, batch_size=4)
    reqs = [Request("a", make_prompt(rng, cfg, 6), max_new_tokens=3)]
    out = eng.generate(reqs)
    assert len(out) == 1 and out[0].done
    assert len(out[0].generated) == 3


def test_generate_mixed_lengths_done_exact(cfg, params):
    rng = np.random.default_rng(1)
    eng = make_engine(cfg, params, batch_size=3)
    reqs = [Request("a", make_prompt(rng, cfg, 5), max_new_tokens=2),
            Request("b", make_prompt(rng, cfg, 8), max_new_tokens=7),
            Request("c", make_prompt(rng, cfg, 6), max_new_tokens=4)]
    out = eng.generate(reqs)
    # done flips at exactly max_new_tokens — never an over-append
    for r in out:
        assert r.done and len(r.generated) == r.max_new_tokens
    # early exit: N tokens need N-1 decode steps (first token from prefill)
    assert eng.metrics["decode_steps"] == max(r.max_new_tokens
                                              for r in reqs) - 1


def test_generate_token_metric_counts_only_live_rows(cfg, params):
    rng = np.random.default_rng(2)
    eng = make_engine(cfg, params, batch_size=3)
    reqs = [Request("a", make_prompt(rng, cfg, 6), max_new_tokens=2),
            Request("b", make_prompt(rng, cfg, 6), max_new_tokens=9)]
    eng.generate(reqs)
    # exactly the tokens delivered — not len(requests) x steps
    assert eng.metrics["tokens"] == sum(r.max_new_tokens for r in reqs)


def test_generate_batched_vs_single_parity(cfg, params):
    """Equal-length prompts batched together decode exactly as alone."""
    rng = np.random.default_rng(3)
    prompts = [make_prompt(rng, cfg, 7) for _ in range(3)]
    eng = make_engine(cfg, params, batch_size=3)
    batched = eng.generate([Request(f"b{i}", p, max_new_tokens=5)
                            for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        solo = make_engine(cfg, params, batch_size=1)
        [ref] = solo.generate([Request("s", p, max_new_tokens=5)])
        assert ref.generated == batched[i].generated


def test_generate_structured_refusals(cfg, params):
    eng = make_engine(cfg, params, batch_size=2, max_seq=32)
    with pytest.raises(AdmissionRefused) as ei:
        eng.generate([Request("long", np.ones(40, np.int32))])
    assert ei.value.code == ErrorCode.BAD_REQUEST
    assert "exceeds max_seq" in str(ei.value)
    with pytest.raises(AdmissionRefused):
        eng.generate([Request("empty", np.zeros(0, np.int32))])
    with pytest.raises(AdmissionRefused) as ei:
        # prompt fits but prompt + max_new overflows the cache
        eng.generate([Request("ovf", np.ones(30, np.int32),
                              max_new_tokens=10)])
    assert "kv cache overflow" in str(ei.value)
    with pytest.raises(AdmissionRefused):
        eng.generate([Request(f"x{i}", np.ones(4, np.int32))
                      for i in range(3)])   # group > batch_size


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
        solo = make_engine(cfg, params, batch_size=1)
        [ref] = solo.generate([Request("s", r.prompt,
                                       max_new_tokens=r.max_new_tokens)])
        assert ref.generated == r.generated, r.request_id


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
        solo = ServingEngine(cfg, params=params, batch_size=1,
                             max_seq=MAX_SEQ)
        [ref] = solo.generate([Request("s", r.prompt,
                                       max_new_tokens=r.max_new_tokens)])
        assert ref.generated == r.generated, r.request_id


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
