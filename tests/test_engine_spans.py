"""The serving engine's host spans and counters.

Each phase of the engine is a named ``TraceAnnotation`` (``serving/spans.py``)
so a profile puts device-idle time down to a host phase; ``engine.metrics``
counts the lock wait, the queue wait, the host time per step, the live rows,
the rows of resident state carried and the first dispatch of each program
shape.  These tests read a profile
of a tiny paged engine and check the counters against what the engine was
given and what it dispatched.
"""
import glob
import sys
import threading

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.simclock import VirtualClock
from repro.models import model_specs
from repro.models.common import init_params
from repro.serving import Request, ServingEngine

DRIVER_SPANS = {"engine.step", "engine.admit", "engine.prime",
                "engine.prepare", "engine.decode", "engine.sample",
                "engine.emit", "engine.park"}


@pytest.fixture(scope="module")
def attn():
    cfg = reduced(get_config("internlm2-20b"))
    return cfg, init_params(model_specs(cfg), seed=1)


def paged_engine(attn, **kw):
    cfg, params = attn
    return ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                         paged=True, page_size=8, pool_pages=64, **kw)


def prompts(cfg, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def record_shapes(eng):
    """The shape of every prime and decode dispatch, seen from outside."""
    seen = []
    prime, past, decode = eng._prime, eng._prime_past, eng._decode

    def on_prime(params, batch, *a):
        seen.append(("prime", batch["tokens"].shape[1]))
        return prime(params, batch, *a)

    def on_past(params, batch, cache, fresh, shared, slot):
        seen.append(("prime_past", batch["tokens"].shape[1],
                     len(shared) * eng.page_size))
        return past(params, batch, cache, fresh, shared, slot)

    def on_decode(params, cache, tokens, pos, tables):
        seen.append(("decode", tables.shape[1]))
        return decode(params, cache, tokens, pos, tables)

    eng._prime, eng._prime_past, eng._decode = on_prime, on_past, on_decode
    return seen


def serve(eng, batch, max_new=6):
    reqs = [eng.submit(Request(f"r{i}", p, max_new_tokens=max_new))
            for i, p in enumerate(batch)]
    eng.drain()
    assert all(r.done for r in reqs)
    return reqs


def test_counters_hold_their_identities(attn):
    cfg, _ = attn
    eng = paged_engine(attn)
    seen = record_shapes(eng)
    lengths = (5, 12, 9, 12)
    first = serve(eng, prompts(cfg, 1, lengths))
    after_first = eng.metrics["new_shapes"]
    assert after_first == len(set(seen)) > 0
    # fresh prompts of the same lengths repeat every shape: nothing new
    serve(eng, prompts(cfg, 2, lengths))
    assert eng.metrics["new_shapes"] == after_first
    # a prompt extending a served one hits the prefix cache: one new
    # (suffix, prefix) pair
    ext = np.concatenate([first[1].prompt, prompts(cfg, 3, (5,))[0]])
    serve(eng, [ext])
    assert any(s[0] == "prime_past" for s in seen)
    assert eng.metrics["new_shapes"] == len(set(seen))

    m = eng.metrics
    n = 2 * len(lengths) + 1
    assert m["submits"] == n and m["primes"] == n and m["requests"] == n
    decodes = sum(1 for s in seen if s[0] == "decode")
    assert m["decode_steps"] == decodes
    assert m["tokens"] == m["primes"] + m["decode_rows"]
    assert m["decode_rows"] <= 2 * m["decode_steps"]
    for key in ("lock_wait_ms", "queue_ms", "host_ms"):
        assert m[key] >= 0.0, key
    assert m["host_ms"] > 0.0


@pytest.mark.parametrize("arch, paged, rows", [
    ("rwkv6-7b", False, 3),        # slot-granular: every row, live or not
    ("internlm2-20b", True, 0),    # every leaf pages: no resident rows
])
def test_state_rows_counts_resident_rows(arch, paged, rows):
    cfg = reduced(get_config(arch))
    eng = ServingEngine(cfg, params=init_params(model_specs(cfg), seed=1),
                        batch_size=3, max_seq=64, paged=paged, page_size=8)
    serve(eng, prompts(cfg, 5, (5, 12)), max_new=4)
    m = eng.metrics
    assert m["decode_steps"] > 0
    assert m["decode_rows"] < 3 * m["decode_steps"]    # a row stayed dead
    assert m["state_rows"] == rows * m["decode_steps"]


def test_virtual_clock_stamps_and_queue_wait(attn):
    cfg, params = attn
    clk = VirtualClock()
    eng = ServingEngine(cfg, params=params, batch_size=2, max_seq=64,
                        paged=True, page_size=8, pool_pages=16, clock=clk)
    r = Request("v", prompts(cfg, 4, (6,))[0], max_new_tokens=3)
    eng.submit(r)
    clk.advance(1.5)                        # queue wait, in virtual time
    eng.drain()
    assert r.arrived_s == 0.0 and r.enqueued_s == 0.0
    assert r.first_token_s == pytest.approx(1.5)
    assert r.finished_s == pytest.approx(1.5)
    assert r.ttft_ms == pytest.approx(1500.0)
    assert eng.metrics["queue_ms"] == pytest.approx(1500.0)
    assert clk.monotonic() == pytest.approx(1.5)


def _host_lines(path):
    """``{line id: [(name, start_ns, end_ns, stats)]}`` of the engine spans
    on the host planes of a profile."""
    from jax.profiler import ProfileData

    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name.split("#", 1)[0], e.start_ns,
                    e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("engine.")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_land_in_the_profile(attn, tmp_path):
    """Many caller threads submit while a driver thread serves; the profile
    holds every driver span on one line, nested as the engine runs them,
    and each caller's lock wait on its own line.  The counters, updated
    under the engine's lock, lose no update."""
    import jax

    cfg, _ = attn
    eng = paged_engine(attn)
    callers, lengths = 8, (5, 9)
    done = threading.Semaphore(0)
    eng.on_complete = lambda r: done.release()
    stop = threading.Event()
    batches = [prompts(cfg, 10 + c, lengths) for c in range(callers)]

    def caller(c):
        for j, p in enumerate(batches[c]):
            eng.submit(Request(f"c{c}-{j}", p, max_new_tokens=3))

    driver = threading.Thread(target=eng.serve_forever, args=(stop,),
                              daemon=True)
    switch = sys.getswitchinterval()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sys.setswitchinterval(1e-5)
        driver.start()
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        for _ in range(callers * len(lengths)):
            assert done.acquire(timeout=120), "engine did not finish"
    finally:
        sys.setswitchinterval(switch)
        stop.set()
        eng.wake()
        driver.join(timeout=10)
        jax.profiler.stop_trace()
    assert not driver.is_alive()

    n = callers * len(lengths)
    m = eng.metrics
    assert m["submits"] == n and m["primes"] == n and m["requests"] == n
    assert m["tokens"] == m["primes"] + m["decode_rows"] == 3 * n

    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _host_lines(path)
    drivers = [k for k, evs in lines.items()
               if any(e[0] == "engine.step" for e in evs)]
    assert len(drivers) == 1
    drv = lines[drivers[0]]
    assert DRIVER_SPANS <= {e[0] for e in drv}
    steps = [e for e in drv if e[0] == "engine.step"]
    admits = [e for e in drv if e[0] == "engine.admit"]
    for e in drv:
        if e[0] in ("engine.admit", "engine.prepare", "engine.decode",
                    "engine.sample"):
            assert any(_inside(e, s) for s in steps), e
        if e[0] == "engine.prime":
            assert any(_inside(e, a) for a in admits), e
    # the first dispatch of a shape is tagged where it lands
    assert any(e[3].get("new_shape") == 1 for e in drv
               if e[0] in ("engine.prime", "engine.decode"))
    waits = [k for k, evs in lines.items()
             if any(e[0] == "engine.submit.lock" for e in evs)]
    assert waits and drivers[0] not in waits
    for k in waits:
        subs = [e for e in lines[k] if e[0] == "engine.submit"]
        for e in lines[k]:
            if e[0] == "engine.submit.lock":
                assert any(_inside(e, s) for s in subs), e
