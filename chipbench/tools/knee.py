"""Knee sweep: the cell's traffic at several fixed rates, one process.

    python3 chipbench/tools/knee.py --workload <cell> --rates 1,2,3 --seconds 30

Sets the cell up once, then plays one window per rate (each drained before
the next) and prints, per rate, the requests due and failed, the TTFT and
TPOT medians and 90th percentiles, output tokens per second, and the TTFT
of the window's last tenth against its first (a backlog that grows through
the window shows as a ratio well above 1).  The knee is the highest rate
with no failures and no growing backlog; the cells run at about 0.8 of it.
Last it prints the device, with its peak memory over the sweep.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from chipbench.harness import bench, device, stats, traffic

    spec_b = bench.load_benchmark()
    cell = next(w for w in spec_b["workloads"] if w["name"] == args.workload)
    device.enable_compile_cache()
    devs = device.require_chips(cell["chips"])
    cfg = bench.load_config(spec_b, cell["config"])
    spec = traffic.load(cell["traffic"])
    compiles = bench.CompileCounter()
    setup = bench.Setup(cfg, spec, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = time.monotonic()
        w = bench.serve_window(setup, cell["config"], args.seed + k,
                               args.seconds, compiles, rate=rate)
        st = w["stats"]
        win = [r for r in stats.in_window(w["records"], args.seconds)
               if stats.ok(r)]
        tenth = max(1, len(win) // 10)
        first = stats.pct([stats.ttft_ms(r) for r in win[:tenth]], 50) \
            if win else float("nan")
        last = stats.pct([stats.ttft_ms(r) for r in win[-tenth:]], 50) \
            if win else float("nan")
        row = {k2: st[k2] for k2 in (
            "attempted", "failed", "ttft_p50_ms", "ttft_p90_ms",
            "tpot_p50_ms", "tpot_p90_ms", "out_tok_s", "late_max_ms",
            "errors")}
        row.update(rate_rps=rate, ttft_last_over_first=last / first,
                   decode_steps=w["engine1"]["decode_steps"]
                   - w["engine0"]["decode_steps"],
                   in_window=w["in_window"],
                   wall_s=round(time.monotonic() - t, 1))
        print("knee " + json.dumps(row), flush=True)
    print("knee device " + json.dumps(device.info(devs)), flush=True)
    setup.plane.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
