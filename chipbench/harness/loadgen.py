"""Open-loop load generator: the child process that plays a schedule.

    python chipbench/harness/loadgen.py <schedule.json> <gateway-url>

It imports no JAX, so it neither holds the chip nor shares the server's
GIL.  It prints ``ready``, reads the window's opening time (the shared
``time.monotonic`` clock) from standard input, then sends every request of
the schedule at its due time through ``ControlPlaneClient.invoke``, one
thread per request.  Each request is timed from its due time, not from
when it was sent.  After the last due time it waits for every answer until
the drain deadline in the schedule, then prints one JSON record per
request and a last line ``{"done": true}``.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from repro.core.tasks import TaskRequest  # noqa: E402
from repro.gateway.client import ControlPlaneClient, GatewayError  # noqa: E402


def _one(client, req, t0, out):
    due = t0 + req["due_s"]
    task = TaskRequest(task_id=f"r{req['i']}", function="generate",
                       input_modality="tokens", output_modality="tokens",
                       payload={"prompt": req["prompt"],
                                "max_new_tokens": req["max_new_tokens"]})
    rec = {"i": req["i"], "late_ms": (time.monotonic() - due) * 1e3}
    try:
        res, _ = client.invoke(task, backpressure_retries=0)
        rec["latency_ms"] = (time.monotonic() - due) * 1e3
        rec["done_s"] = time.monotonic() - t0
        rec["status"] = res.status
        out_ = res.output if isinstance(res.output, dict) else {}
        rec["tokens"] = list(out_.get("tokens") or [])
        rec["total_ms"] = out_.get("total_ms")
        rec["ttft_ms"] = (res.telemetry or {}).get("ttft_ms")
    except GatewayError as e:
        rec["status"] = "refused"
        rec["error"] = f"{e.code}: {e}"[:300]
    except Exception as e:                                     # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    out[req["i"]] = rec


def main() -> int:
    sched = json.loads(Path(sys.argv[1]).read_text())
    client = ControlPlaneClient(sys.argv[2], timeout_s=sched["timeout_s"])
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    out, threads = {}, []
    for req in sched["requests"]:
        delay = t0 + req["due_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=_one, args=(client, req, t0, out),
                              daemon=True)
        th.start()
        threads.append(th)
    drain_at = t0 + sched["drain_until_s"]
    for th in threads:
        th.join(max(0.0, drain_at - time.monotonic()))
    for req in sched["requests"]:
        rec = out.get(req["i"], {"i": req["i"], "status": "unfinished"})
        print(json.dumps(rec), flush=True)
    print(json.dumps({"done": True}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
