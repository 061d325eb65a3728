"""Benchmark harness: one bench per paper table/figure + framework extras.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows and writes machine-readable
JSON under benchmarks/results/.

Paper artifact map:
    bench_portability — Table III + RQ1 shared-key ratios
    bench_matcher     — RQ2 selector comparison (7-task suite)
    bench_faults      — Table IV fault campaign
    bench_overhead    — RQ3 local control-path cost (25 runs × 3 backends)
    bench_http        — RQ3 externalized HTTP path (15 invocations)
    bench_cortical    — §VIII-A/C Cortical Labs end-to-end (3 directed runs)
    bench_roofline    — EXPERIMENTS.md §Roofline table (dry-run cache)
    bench_fleet       — beyond-paper orchestrated TPU-fleet training
    bench_throughput  — beyond-paper sustained throughput: serial submit
                        loop vs pooled ControlPlaneScheduler
    bench_recovery    — beyond-paper resilience: goodput under faults with
                        vs without the HealthManager (circuit breakers)
    bench_twin        — beyond-paper executable twins: goodput retained
                        under quarantine with twin-served fallback vs the
                        reject-only baseline (same fault schedule as
                        bench_recovery; zero-invalid-serves audited)
    bench_gateway     — beyond-paper wire API: control-path overhead of the
                        gateway + client SDK vs the in-process plane
                        (reproduces the paper's "small control-path
                        overhead" across a real protocol boundary)
    bench_hierarchy   — beyond-paper multi-hop federation: per-hop added
                        control latency on a device→edge→fog→cloud chain
                        (vs the single-hop wire margin) and streaming
                        telemetry fan-in vs the N-cursor polling baseline
                        (request count + zero-loss by sequence numbers)
    bench_serving     — beyond-paper LM serving substrate: paged-KV
                        parity, capacity and prefix TTFT against the
                        slot-granular layout on a mixed-length arrival
                        trace, p99 TTFT + structured DEADLINE admission
                        refusals under >= 128 concurrent gateway sessions
                        (zero mid-decode expiries for admitted requests)
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import (bench_cortical, bench_faults, bench_fleet,
                        bench_gateway, bench_hierarchy, bench_http,
                        bench_matcher, bench_overhead, bench_portability,
                        bench_recovery, bench_roofline, bench_scenarios,
                        bench_serving, bench_throughput, bench_twin)

BENCHES = {
    "portability": bench_portability.run,
    "matcher": bench_matcher.run,
    "faults": bench_faults.run,
    "overhead": bench_overhead.run,
    "http": bench_http.run,
    "cortical": bench_cortical.run,
    "roofline": bench_roofline.run,
    "fleet": bench_fleet.run,
    "throughput": bench_throughput.run,
    "recovery": bench_recovery.run,
    "twin": bench_twin.run,
    "gateway": bench_gateway.run,
    "hierarchy": bench_hierarchy.run,
    "serving": bench_serving.run,
    "scenarios": bench_scenarios.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()

    from repro.substrates.http_fast import FastService
    svc = FastService().start()
    print("name,us_per_call,derived")
    try:
        for name, fn in BENCHES.items():
            if args.only and name != args.only:
                continue
            for row in fn(svc):
                print(row, flush=True)
    finally:
        svc.stop()


if __name__ == '__main__':
    main()
