"""Record one cell's traced run and put the device's idle time down to the
engine's host spans.

    python3 chipbench/tools/idle_split.py --workload <cell> --seed 1 \
        --seconds 51 --trace-seconds 4 --out <dir>

Runs the cell once with ``--trace 1``, keeps what ``record_trace.py`` keeps
in ``--out`` (the raw trace, ``sync.json``, ``summary.json``,
``result.json``), and prints and writes to ``spans.json``:

- the traced window's device-idle time split by the innermost span of the
  engine's driver thread open at each idle instant, and ``idle_host_pct``
  (``harness/host_spans.py``);
- the measured window's live rows per decode step, the program shapes
  first dispatched in it and its primes, from the engine's counters at its
  edges, beside the primes the harness's call log recorded;
- what tracing costs the spans: host time, decode and prime per step
  inside the traced sub-window (from its spans) against the rest of the
  measured window (the engine's counters less the traced part).
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.monotonic()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def costs(spans, lo, hi, engine0, engine1):
    """Host time, decode and prime per step inside the traced window (the
    driver's steps that lie in it, from their spans) and in the rest of the
    measured window (the engine's counters less the traced part)."""
    steps = [s for s in spans
             if s.name == "engine.step" and lo <= s.start and s.end <= hi]
    ms, n = {"engine.step": 0.0, "engine.prime": 0.0, "engine.decode": 0.0}, {}
    for s in spans:
        if s.name in ms and any(t.start <= s.start and s.end <= t.end
                                for t in steps):
            ms[s.name] += 1e3 * s.dur
            n[s.name] = n.get(s.name, 0) + 1
    host_in = ms["engine.step"] - ms["engine.prime"] - ms["engine.decode"]

    def d(key):
        return float(engine1.get(key, 0)) - float(engine0.get(key, 0))

    steps_in = n.get("engine.decode", 0)
    primes_in = n.get("engine.prime", 0)
    steps_out = d("decode_steps") - steps_in
    primes_out = d("primes") - primes_in
    out = {"decode_steps_traced": steps_in, "decode_steps_rest": steps_out}
    if steps_in and steps_out > 0:
        out.update(
            host_ms_per_step_traced=host_in / steps_in,
            host_ms_per_step_rest=(d("host_ms") - host_in) / steps_out,
            decode_ms_traced=ms["engine.decode"] / steps_in,
            decode_ms_rest=(d("decode_ms") - ms["engine.decode"]) / steps_out)
    if primes_in and primes_out > 0:
        out.update(prime_ms_traced=ms["engine.prime"] / primes_in,
                   prime_ms_rest=(d("prefill_ms") - ms["engine.prime"])
                   / primes_out)
    return out


def report(out_dir: str, window, workload: str, seed: int):
    """The split, the window's counters and the spans' costs, printed and
    written to ``spans.json``; ``window`` is what ``serve_window`` returned
    for the run whose trace ``out_dir`` keeps."""
    from chipbench.harness import host_spans as hs

    sync = json.loads(Path(out_dir, "sync.json").read_text())
    spans = hs.host_spans(os.path.join(out_dir, "trace.xplane.pb"),
                          sync["sync"])
    red = window["trace"]
    e0, e1 = window["engine0"], window["engine1"]

    def d(key):
        return float(e1.get(key, 0)) - float(e0.get(key, 0))

    steps = d("decode_steps")
    out = {"workload": workload, "seed": seed,
           "split_s": hs.idle_split(red, spans),
           "idle_host_pct": hs.idle_host_pct(red, spans),
           "rows_per_step": d("decode_rows") / steps if steps else None,
           "new_shapes": d("new_shapes"),
           "primes": d("primes"),
           "prime_calls": sum(1 for c in window["calls"]
                              if c.kind == "prime"),
           "costs": costs(hs.driver(spans), *red.window, e0, e1)}
    print(f"chipbench idle split: {hs.split_line(red, spans)}", flush=True)
    print(f"chipbench window: rows per decode step {out['rows_per_step']}, "
          f"new shapes {out['new_shapes']:.0f}, primes {out['primes']:.0f} "
          f"(calls recorded from outside: {out['prime_calls']})", flush=True)
    print(f"chipbench spans traced / rest: {out['costs']}", flush=True)
    with open(os.path.join(out_dir, "spans.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench.harness import bench, device

    spec = bench.load_benchmark()
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    device.enable_compile_cache()
    devs = device.require_chips(cell["chips"])
    bench.TRACE_S = args.trace_seconds
    bench.KEEP_TRACE = args.out
    windows = []
    serve = bench.serve_window

    def kept(*a, **kw):
        windows.append(serve(*a, **kw))
        return windows[-1]

    bench.serve_window = kept
    result = bench.run_cell(spec, args.workload, args.seed, args.seconds,
                            True, devs, T_START,
                            log=lambda s: print(s, flush=True))
    with open(os.path.join(args.out, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    report(args.out, windows[-1], args.workload, args.seed)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
