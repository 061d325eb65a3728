"""Readings that set a configuration's limit on the output check.

    python3 chipbench/tools/limits.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 15

Sets the cell up once, then for each seed draws that seed's weights, plays
a short window of the cell's own traffic at its own rate, and prints the
numbers the output check compares (the widest logit gap of the served
tokens against the float32 reference).  For the control seeds it also runs
the control on the same prompts and tokens: the reference at float8, and
the gap of the token that it puts first.  The lower reading is the largest
program gap over the seeds, the upper the smallest control gap.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()

    from chipbench.harness import bench, device, traffic

    spec_b = bench.load_benchmark()
    cell = next(w for w in spec_b["workloads"] if w["name"] == args.workload)
    device.enable_compile_cache()
    device.require_chips(cell["chips"])
    cfg = bench.load_config(spec_b, cell["config"])
    spec = traffic.load(cell["traffic"])
    compiles = bench.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    setup = bench.Setup(cfg, spec, seeds[0])
    for seed in seeds:
        t = time.monotonic()
        if seed != seeds[0]:
            setup.set_weights(seed)
        w = bench.serve_window(setup, cell["config"], seed, args.seconds,
                               compiles)
        setup.plane.engine.flush()        # frees the decode cache
        nums = bench.check_numbers(setup, w["records"], seed,
                                   control=seed in controls)
        nums.update(seed=seed, attempted=w["stats"]["attempted"],
                    failed=w["stats"]["failed"],
                    wall_s=round(time.monotonic() - t, 1))
        print("limits " + json.dumps(nums), flush=True)
    setup.plane.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
