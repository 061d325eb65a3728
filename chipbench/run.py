"""Chip benchmark of the LM serving substrate: one cell, one run.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for (``BENCHMARK.json``).  Serves the cell's configuration through the
control plane and plays its traffic open loop from a child process over the
gateway, then checks a sample of the served tokens against the plain
float32 reference.  Earlier lines (standard output) give the window's
medians, the generator's lateness and the compilations inside the window;
the last lines of standard error give each number compared beside its
limit; the last line of standard output is the result, one JSON object.
With ``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  With no TPU, or fewer chips than
the cell needs, it exits non-zero and prints no result.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.monotonic()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench.harness import bench, device

    spec = bench.load_benchmark()
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    devs = device.require_chips(cell["chips"])
    result = bench.run_cell(spec, args.workload, args.seed, args.seconds,
                            bool(args.trace), devs, T_START,
                            log=lambda s: print(s, flush=True))
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
