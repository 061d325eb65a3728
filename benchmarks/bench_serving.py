"""LM serving bench: the paged KV layout and admission under load.

Two sections, each with its asserts, 3 committed trials in
``results/bench_serving.json``.  Every figure is a CPU figure at the toy
``reduced()`` widths, not a device metric.

- **concurrency** — one ``LmServingAdapter`` behind a real
  ``ControlPlaneGateway``; ``SESSIONS`` (>= 128 full-run) client threads
  share one SDK client and ride ``invoke_coalesced`` (submit coalescing +
  long-poll mux).  One request in ``DOOMED_EVERY`` carries a deadline
  budget the roofline admission model cannot meet — those must come back
  as structured ``DEADLINE`` refusals, never tie up batch slots, and
  never trip the breaker for everyone else.  Asserts: every doomed
  request refused as ``DEADLINE``, every admitted request completed,
  p99 engine TTFT within ``TTFT_P99_BOUND_MS``, and **zero mid-decode
  deadline expiries for admitted requests** (the admission model's whole
  point: refuse at the door, never renege mid-decode).

- **paged** — the paged-KV cache (PR 10) against the slot-granular layout
  it replaces, same params, four sub-claims:

  * *parity/goodput* — a mixed-length arrival trace (small prompt-length
    set so prefill compiles stay bounded; heavy-tailed ``max_new_tokens``)
    served on both layouts must be token-for-token identical, at >= 1.0x
    goodput (generated tokens / wall second) with the paged pool holding a
    fraction of the slot-granular KV HBM.  The paged
    producer uses admission backpressure: on ``QUEUE_SATURATED`` it steps
    the engine and retries, so the pool only covers live + queued
    reservations (batch x worst-case pages per request), not the whole
    trace — which is the layout's entire point;
  * *capacity* — under the SAME KV HBM budget, 64-token requests admit 2x
    deeper: the slot-granular engine burns a full 128-token row per
    request, the paged engine only the 4 pages each actually needs;
  * *prefix TTFT* — a prefix-heavy trace (64-token shared prefix, short
    suffixes) with prefix sharing on vs off: suffix-only prefill must cut
    p50 TTFT by >= 30%;
  * *saturation* — overfilling the pool refuses with structured
    ``QUEUE_SATURATED`` + ``retry_after_s``, and a drained engine audits
    zero leaked pages.

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]

``--smoke`` (make serving-smoke, CI) shrinks the trace and session count,
keeps every correctness assert (refusal taxonomy, zero expiries, admitted
completion, paged token parity, saturation taxonomy, leak audit) and drops
only the perf bounds — tiny traces make the ratios noisy, and CI machines
should not fail on throughput weather.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List

from benchmarks.common import csv_row, save

N_TRIALS = 3

# -- mixed-length trace (full run) --------------------------------------------
BATCH = 8
MAX_SEQ = 128
N_REQS = 64
PROMPT_LENS = (6, 7, 8, 9)        # small set: prefill compiles stay bounded
LIGHT_MAX_NEW = (2, 3)
HEAVY_MAX_NEW = 64                # the heavy tail of decode budgets
HEAVY_EVERY = 8                   # 1 in 8 requests is heavy

# -- paged kv -----------------------------------------------------------------
PAGE_SIZE = 16
LONG_PROMPT = 24                  # long-request shape: 24 prompt + 40 decode
LONG_MAX_NEW = 40                 # = 64 tokens = 4 pages of 16
PREFIX_LEN = 64                   # shared prefix of the prefix-heavy trace
N_PREFIX_REQS = 16
PAGED_GOODPUT_MIN = 1.0
CAPACITY_RATIO_MIN = 2.0
TTFT_REDUCTION_MIN = 0.30

# -- gateway concurrency ------------------------------------------------------
SESSIONS = 128
WORKERS = 64
DOOMED_EVERY = 8
DOOMED_BUDGET_MS = 20.0           # cannot cover HEAVY_MAX_NEW decode steps
ADMITTED_BUDGET_MS = 60_000.0     # generous but real: expiry bookkeeping on
TTFT_P99_BOUND_MS = 2_000.0

ARCH = "internlm2-20b"


def _pct(xs: List[float], p: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(p * (len(xs) - 1)))]


def _trace(rng, cfg, n_reqs: int, heavy_max_new: int):
    """Mixed-length arrival trace: (prompt, max_new) pairs, heavy-tailed."""
    out = []
    for i in range(n_reqs):
        plen = int(rng.choice(PROMPT_LENS))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype("int32")
        max_new = heavy_max_new if i % HEAVY_EVERY == HEAVY_EVERY - 1 \
            else int(rng.choice(LIGHT_MAX_NEW))
        out.append((prompt, max_new))
    return out


def _paged_section(smoke: bool) -> Dict:
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core.errors import AdmissionRefused, ErrorCode
    from repro.models import model_specs
    from repro.models.common import init_params
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config(ARCH))
    params = init_params(model_specs(cfg), seed=1)

    def continuous(eng, trace, tag):
        """Submit with admission backpressure: a ``QUEUE_SATURATED``
        refusal steps the engine (freeing pages) and retries — the
        work-conserving client loop the structured refusal is for.  An
        engine without a pool never refuses, so the dense baseline runs
        the identical loop."""
        reqs = [Request(f"{tag}{i}", p, max_new_tokens=mn)
                for i, (p, mn) in enumerate(trace)]
        pending = deque(reqs)
        t0 = time.perf_counter()
        while pending:
            try:
                eng.submit(pending[0])
                pending.popleft()
            except AdmissionRefused:
                eng.step()
        eng.drain()
        wall_s = time.perf_counter() - t0
        assert all(r.done and len(r.generated) == r.max_new_tokens
                   for r in reqs)
        tokens = sum(len(r.generated) for r in reqs)
        ttfts = [r.ttft_ms for r in reqs]
        stats = {"tokens": tokens, "wall_s": round(wall_s, 4),
                 "tokens_per_s": round(tokens / wall_s, 2),
                 "ttft_p50_ms": round(_pct(ttfts, 0.50), 3)}
        return stats, [r.generated for r in reqs]

    # 1) parity + goodput: the mixed-length trace on both layouts ------------
    batch = 4 if smoke else BATCH
    n_reqs = 12 if smoke else N_REQS
    heavy = 24 if smoke else HEAVY_MAX_NEW
    trace = _trace(np.random.default_rng(7), cfg, n_reqs, heavy)
    # pool sized for live work only: batch x worst-case pages per request.
    # Backpressure in ``continuous`` holds the rest of the trace at the
    # door, so the paged engine serves the same trace in a fraction of the
    # slot-granular KV HBM (batch x MAX_SEQ tokens).
    pool = batch * max(-(-(len(p) + mn) // PAGE_SIZE) for p, mn in trace)
    hbm_fraction = pool * PAGE_SIZE / (batch * MAX_SEQ)
    dense_eng = ServingEngine(cfg, params=params, batch_size=batch,
                              max_seq=MAX_SEQ)
    paged_eng = ServingEngine(cfg, params=params, batch_size=batch,
                              max_seq=MAX_SEQ, paged=True,
                              page_size=PAGE_SIZE, pool_pages=pool)
    continuous(dense_eng, trace, "w")          # compile warmup, both paths
    continuous(paged_eng, trace, "w")
    trials = []
    for _ in range(1 if smoke else N_TRIALS):
        dense, dense_out = continuous(dense_eng, trace, "d")
        paged, paged_out = continuous(paged_eng, trace, "p")
        assert paged_out == dense_out, \
            "paged decode diverged from slot-granular (token parity)"
        trials.append({"dense": dense, "paged": paged,
                       "goodput_ratio": round(paged["tokens_per_s"]
                                              / dense["tokens_per_s"], 4)})
    ratios = [t["goodput_ratio"] for t in trials]
    if not smoke:
        assert max(ratios) >= PAGED_GOODPUT_MIN, \
            f"paged goodput ratio {max(ratios):.3f} < {PAGED_GOODPUT_MIN}x " \
            f"of the slot-granular path"

    # 2) capacity: same KV HBM, 2x the concurrent long requests -------------
    cap_batch = 4
    hbm_tokens = cap_batch * MAX_SEQ               # slot-granular KV budget
    n_long = 2 * cap_batch
    rng = np.random.default_rng(21)
    long_trace = [(rng.integers(1, cfg.vocab_size,
                                size=LONG_PROMPT).astype("int32"),
                   LONG_MAX_NEW) for _ in range(n_long)]
    dense_cap = ServingEngine(cfg, params=params, batch_size=cap_batch,
                              max_seq=MAX_SEQ)
    paged_cap = ServingEngine(cfg, params=params, batch_size=n_long,
                              max_seq=MAX_SEQ, paged=True,
                              page_size=PAGE_SIZE,
                              pool_pages=hbm_tokens // PAGE_SIZE,
                              prefix_sharing=False)
    for eng in (dense_cap, paged_cap):
        for r in [Request(f"c{i}", p, max_new_tokens=mn)
                  for i, (p, mn) in enumerate(long_trace)]:
            eng.submit(r)                          # all reservations fit
        eng.step()                                 # admit as deep as layout allows
    dense_live, paged_live = dense_cap.live_slots(), paged_cap.live_slots()
    dense_cap.drain()
    paged_cap.drain()
    capacity_ratio = paged_live / dense_live
    assert capacity_ratio >= CAPACITY_RATIO_MIN, \
        f"paged concurrent capacity {paged_live} vs {dense_live} " \
        f"({capacity_ratio:.2f}x < {CAPACITY_RATIO_MIN}x at equal HBM)"
    assert paged_cap.audit_pages()["used"] == 0
    capacity = {"kv_hbm_tokens": hbm_tokens,
                "request_tokens": LONG_PROMPT + LONG_MAX_NEW,
                "dense_concurrent": dense_live,
                "paged_concurrent": paged_live,
                "capacity_ratio": capacity_ratio}

    # 3) prefix-heavy trace: suffix-only prefill cuts TTFT -------------------
    rng = np.random.default_rng(22)
    prefix = rng.integers(1, cfg.vocab_size, size=PREFIX_LEN).astype("int32")
    n_pref = 8 if smoke else N_PREFIX_REQS
    pref_trace = [(np.concatenate([prefix, rng.integers(
        1, cfg.vocab_size, size=4 + i % 4).astype("int32")]), 8)
        for i in range(n_pref)]
    # full-queue pool: every request admits up front, so TTFT differences
    # are prefill cost, not admission backpressure.  Wide batch keeps the
    # queue shallow — deep queues bury the prefill saving under decode
    # wait that both engines pay identically.
    pref_pool = sum(-(-(len(p) + mn) // PAGE_SIZE) for p, mn in pref_trace)
    kw = dict(batch_size=BATCH, max_seq=MAX_SEQ, paged=True,
              page_size=PAGE_SIZE, pool_pages=pref_pool)
    cold_eng = ServingEngine(cfg, params=params, prefix_sharing=False, **kw)
    warm_eng = ServingEngine(cfg, params=params, prefix_sharing=True, **kw)
    continuous(cold_eng, pref_trace, "w")      # compile warmup; also warms
    continuous(warm_eng, pref_trace, "w")      # the prefix cache
    cold, cold_out = continuous(cold_eng, pref_trace, "n")
    warm, warm_out = continuous(warm_eng, pref_trace, "s")
    assert warm_out == cold_out, \
        "prefix-shared decode diverged from private-pages decode"
    ttft_reduction = 1.0 - warm["ttft_p50_ms"] / cold["ttft_p50_ms"]
    if not smoke:
        assert ttft_reduction >= TTFT_REDUCTION_MIN, \
            f"prefix cache cut p50 TTFT by {ttft_reduction:.0%} " \
            f"< {TTFT_REDUCTION_MIN:.0%}"
    prefix_stats = warm_eng.pool_stats()
    prefix_section = {"prefix_len": PREFIX_LEN, "n_requests": n_pref,
                      "no_sharing": cold, "sharing": warm,
                      "ttft_p50_reduction": round(ttft_reduction, 4),
                      "prefix_hit_rate": prefix_stats["prefix_hit_rate"]}

    # 4) saturation: structured refusal + zero-leak audit --------------------
    sat_eng = ServingEngine(cfg, params=params, batch_size=2,
                            max_seq=MAX_SEQ, paged=True,
                            page_size=PAGE_SIZE, pool_pages=8,
                            prefix_sharing=False)
    rng = np.random.default_rng(23)
    held = [sat_eng.submit(Request(f"s{i}", rng.integers(
        1, cfg.vocab_size, size=LONG_PROMPT).astype("int32"),
        max_new_tokens=LONG_MAX_NEW)) for i in range(2)]
    try:
        sat_eng.submit(Request("over", rng.integers(
            1, cfg.vocab_size, size=LONG_PROMPT).astype("int32"),
            max_new_tokens=LONG_MAX_NEW))
        raise AssertionError("pool overfill was not refused")
    except AdmissionRefused as e:
        assert e.code is ErrorCode.QUEUE_SATURATED
        assert e.detail["retry_after_s"] > 0
        refusal = {"code": e.code.value,
                   "retry_after_s": e.detail["retry_after_s"],
                   "needed_pages": e.detail["needed_pages"]}
    sat_eng.drain()
    assert all(r.done for r in held)
    audit = sat_eng.audit_pages()
    assert audit["used"] == 0 and audit["reserved"] == 0, \
        f"page leak after drain: {audit}"

    return {"page_size": PAGE_SIZE, "batch_size": batch,
            "n_requests": n_reqs, "pool_pages": pool,
            "kv_hbm_fraction": round(hbm_fraction, 4), "trials": trials,
            "goodput_ratio_best": max(ratios), "capacity": capacity,
            "prefix": prefix_section,
            "saturation": {"refusal": refusal, "audit": audit}}


def _flood_trial(client, sessions: int) -> Dict:
    """``sessions`` concurrent threads, each one coalesced invoke; a
    deterministic 1-in-``DOOMED_EVERY`` carries an unmeetable budget."""
    from repro.core import TaskRequest
    from repro.core.errors import ErrorCode
    from repro.gateway.client import GatewayError

    lock = threading.Lock()
    completed: List[Dict] = []
    refused: List[str] = []
    unexpected: List[str] = []

    def one(i: int) -> None:
        doomed = i % DOOMED_EVERY == DOOMED_EVERY - 1
        plen = PROMPT_LENS[i % len(PROMPT_LENS)]
        task = TaskRequest(
            function="generate", input_modality="tokens",
            output_modality="tokens",
            payload={"prompt": [1 + (i + j) % 50 for j in range(plen)],
                     "max_new_tokens": HEAVY_MAX_NEW if doomed
                     else 2 + i % 5},
            latency_budget_ms=DOOMED_BUDGET_MS if doomed
            else ADMITTED_BUDGET_MS)
        t0 = time.perf_counter()
        try:
            res, _ = client.invoke_coalesced(task)
            wall_ms = (time.perf_counter() - t0) * 1e3
            with lock:
                completed.append({"doomed": doomed, "wall_ms": wall_ms,
                                  "telemetry": dict(res.telemetry)})
        except GatewayError as e:
            with lock:
                (refused if e.code is ErrorCode.DEADLINE
                 else unexpected).append(f"{'doomed' if doomed else 'ok'}-"
                                         f"{i}: {e.code.value}")

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(sessions)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180.0)
    wall_s = time.perf_counter() - t0
    assert not unexpected, f"non-DEADLINE failures: {unexpected[:5]}"
    n_doomed = sessions // DOOMED_EVERY
    assert not any(c["doomed"] for c in completed) \
        and len(refused) == n_doomed, \
        f"expected {n_doomed} DEADLINE refusals, got {len(refused)} " \
        f"({sum(c['doomed'] for c in completed)} doomed served)"
    assert len(completed) == sessions - n_doomed, \
        f"admitted completions {len(completed)} != {sessions - n_doomed}"
    ttfts = [c["telemetry"]["ttft_ms"] for c in completed]
    walls = [c["wall_ms"] for c in completed]
    expired = sum(bool(c["telemetry"].get("deadline_expired"))
                  for c in completed)
    assert expired == 0, \
        f"{expired} admitted requests expired mid-decode (admission model " \
        f"must refuse at the door instead)"
    return {
        "sessions": sessions, "wall_s": round(wall_s, 3),
        "completed": len(completed), "deadline_refused": len(refused),
        "mid_decode_expiries": expired,
        "ttft_p50_ms": round(_pct(ttfts, 0.50), 3),
        "ttft_p99_ms": round(_pct(ttfts, 0.99), 3),
        "e2e_p50_ms": round(_pct(walls, 0.50), 3),
        "e2e_p99_ms": round(_pct(walls, 0.99), 3),
    }


def _concurrency_section(smoke: bool) -> Dict:
    from repro.configs import get_config, reduced
    from repro.core import Orchestrator, TaskRequest
    from repro.gateway import ControlPlaneClient, ControlPlaneGateway
    from repro.substrates import LmServingAdapter

    sessions = 16 if smoke else SESSIONS
    orch = Orchestrator(plane="serving-bench")
    adapter = LmServingAdapter(reduced(get_config(ARCH)), batch_size=BATCH,
                               max_seq=MAX_SEQ,
                               max_concurrent=max(sessions, 256))
    orch.register(adapter)
    gw = ControlPlaneGateway(orch, plane="serving-bench",
                             workers=WORKERS).start()
    client = ControlPlaneClient(gw.url, timeout_s=120.0)
    try:
        # warm in-process first: builds the engine, compiles prefill for
        # every prompt length the flood uses, seeds the cost model
        for plen in PROMPT_LENS:
            res, _ = orch.execute(TaskRequest(
                function="generate", input_modality="tokens",
                output_modality="tokens",
                payload={"prompt": list(range(1, plen + 1)),
                         "max_new_tokens": 4}))
            assert res.status == "completed"
        trials = [_flood_trial(client, sessions)
                  for _ in range(1 if smoke else N_TRIALS)]
        p99s = [t["ttft_p99_ms"] for t in trials]
        if not smoke:
            assert max(p99s) <= TTFT_P99_BOUND_MS, \
                f"p99 TTFT {max(p99s):.1f}ms over {TTFT_P99_BOUND_MS}ms " \
                f"bound at {sessions} sessions"
        m = adapter.engine.metrics
        assert m["deadline_expired"] == 0
        return {"sessions": sessions, "workers": WORKERS,
                "doomed_every": DOOMED_EVERY,
                "doomed_budget_ms": DOOMED_BUDGET_MS,
                "trials": trials, "ttft_p99_worst_ms": max(p99s),
                "engine_requests": m["requests"],
                "engine_deadline_expired": m["deadline_expired"],
                "cost_model": adapter.cost.snapshot()}
    finally:
        client.close()
        gw.stop()
        adapter.close()


def run(fast_service, smoke: bool = False) -> List[str]:
    del fast_service                    # serving brings its own substrate
    paged = _paged_section(smoke)
    conc = _concurrency_section(smoke)
    payload = {"arch": ARCH, "max_seq": MAX_SEQ, "smoke": smoke,
               "paged": paged, "concurrency": conc}
    save("bench_serving_smoke" if smoke else "bench_serving", payload)
    t0 = conc["trials"][0]
    return [
        csv_row("serving_paged_tokens_per_s",
                max(t["paged"]["tokens_per_s"] for t in paged["trials"]),
                f"vs_dense={paged['goodput_ratio_best']:.2f}x "
                f"at_hbm={paged['kv_hbm_fraction']:.0%} "
                f"capacity={paged['capacity']['capacity_ratio']:.1f}x "
                f"prefix_ttft_cut="
                f"{paged['prefix']['ttft_p50_reduction']:.0%}"),
        csv_row("serving_ttft_p99_ms", conc["ttft_p99_worst_ms"],
                f"sessions={conc['sessions']} "
                f"refused={t0['deadline_refused']} "
                f"expired={conc['engine_deadline_expired']}"),
    ]


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for row in run(None, smoke=args.smoke):
        print(row, flush=True)


if __name__ == "__main__":
    main()
