"""LM serving example: continuous batching on any arch.

Serves a mixed-length request trace through the engine (``submit`` +
``drain``: finished sequences leave the decode batch, freed KV slots are
re-primed from fresh prefills) and prints the per-request TTFT /
tokens-per-second telemetry the engine stamps.

    PYTHONPATH=src python examples/serve_lm.py --arch deepseek-v2-236b
    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-7b --tokens 16
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro import compile_cache
from repro.configs import get_config, reduced
from repro.serving import Request, ServingEngine


def make_requests(rng, cfg, n, tokens):
    """Mixed budgets: every fourth request wants 2x the tokens, so short
    rows leave the batch early and their slots are refilled."""
    return [Request(f"req-{i}",
                    rng.integers(1, cfg.vocab_size, 6 + i % 4).astype(np.int32),
                    max_new_tokens=tokens * 2 if i % 4 == 3 else tokens)
            for i in range(n)]


def report(label, reqs, dt, engine, steps_before=0):
    tokens = sum(len(r.generated) for r in reqs)
    steps = engine.metrics["decode_steps"] - steps_before
    print(f"[{label}] {tokens} tokens in {dt*1e3:.0f}ms "
          f"({tokens/dt:.0f} tok/s, {steps} decode steps)")
    for r in reqs:
        print(f"  {r.request_id}: prompt[{len(r.prompt)}] "
              f"+{len(r.generated)} tokens  ttft={r.ttft_ms:.1f}ms  "
              f"{r.tokens_per_s:.0f} tok/s -> {r.generated[:8]}"
              f"{'...' if len(r.generated) > 8 else ''}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-20b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=12)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = reduced(get_config(args.arch))
    print(f"serving {cfg.name} (reduced config, family={cfg.family})")
    n_reqs = args.batch * 2

    def trace():
        return make_requests(np.random.default_rng(0), cfg, n_reqs,
                             args.tokens)

    engine = ServingEngine(cfg, batch_size=args.batch, max_seq=96)
    for r in trace():                          # warmup: per-length prefills
        engine.submit(r)
    engine.drain()
    reqs = trace()
    steps0 = engine.metrics["decode_steps"]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.drain()
    report("continuous", reqs, time.perf_counter() - t0, engine, steps0)


if __name__ == "__main__":
    main()
