"""Record one cell's traced run and keep its raw profiler trace.

    python3 chipbench/tools/record_trace.py --workload <cell> --seed 1 \
        --seconds 8 --trace-seconds 0.5 --out <dir>

Runs the cell once with ``--trace 1`` and a traced sub-window of
``--trace-seconds``, and writes into ``--out``: ``trace.xplane.pb`` (the
raw trace), ``sync.json`` (its tie to the host clock and the engine calls
it covers), ``summary.json`` (planes, lines and the commonest event names)
and ``result.json`` (the run's result line).  A short trace of this kind,
recorded on the chip, is what the trace reduction's test reads.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.monotonic()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace-seconds", type=float, default=0.5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench.harness import bench, device

    spec = bench.load_benchmark()
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    device.enable_compile_cache()
    devs = device.require_chips(cell["chips"])
    bench.TRACE_S = args.trace_seconds
    bench.KEEP_TRACE = args.out
    result = bench.run_cell(spec, args.workload, args.seed, args.seconds,
                            True, devs, T_START,
                            log=lambda s: print(s, flush=True))
    with open(os.path.join(args.out, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
