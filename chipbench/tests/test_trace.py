"""The trace reduction on a short trace recorded on one TPU v5e chip
(``tools/record_trace.py`` on ``internlm2-1chip.longprompt``, half a second
traced): the same reduction gives back what that run reported, and its
parts hold together."""
import json

import pytest

from chipbench.harness import device, paths, program, record
from chipbench.harness import trace as tr
from chipbench.harness.bench import load_benchmark, load_config, per_layer

DATA = paths.BENCH / "tests" / "data" / "trace"
WORKLOAD = "internlm2-1chip.longprompt"


@pytest.fixture(scope="module")
def recorded():
    sync = json.loads((DATA / "sync.json").read_text())
    result = json.loads((DATA / "result.json").read_text())
    red = tr.reduce(str(DATA / "trace.xplane.pb"), sync["sync"],
                    tuple(sync["window"]))
    calls = [record.Call(c["t"], c["kind"], c["prompt_len"],
                         tuple(c["contexts"])) for c in sync["calls"]]
    return red, calls, result


def test_busy_and_window(recorded):
    red, _, result = recorded
    assert 0 < red.busy_s <= red.window_s
    assert red.busy_s == result["device"]["busy_s"]
    assert red.window_s == result["device"]["window_s"]


def test_programs_and_kernels(recorded):
    red, calls, _ = recorded
    assert red.programs("decode") and red.programs("prime")
    kinds = {c.kind for c in calls}
    assert kinds == {"prime", "decode"}
    # the flash kernel runs inside the admission prefills only
    flash = red.kernels("flash")
    assert flash
    primes = red.programs("prime")
    for k in flash:
        assert any(p.start <= k.start <= p.start + p.dur for p in primes)
    # every traced execution is paired with the host call that made it
    run = record.Run(cfg=None, fam=None, peak=None, seconds=0.0, stats={},
                     engine0={}, engine1={}, calls=[], trace=red,
                     trace_calls=calls)
    for kind in ("prime", "decode"):
        for ev, call in run.matched(kind):
            assert call.kind == kind and call.t <= ev.start + 1e-3


def test_breakdown(recorded):
    red, _, result = recorded
    bd = tr.breakdown(red)
    assert bd == result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    idle = sum(s for _, s in bd["idle_gaps"])
    assert idle <= red.window_s - red.busy_s + 1e-9


def test_device_metrics_as_reported(recorded):
    red, calls, result = recorded
    bench = load_benchmark()
    cfg = load_config(bench, "internlm2-20b-1chip")
    run = record.Run(cfg=cfg, fam=program.family(cfg),
                     peak=device.peaks(result["device"]["kind"]),
                     seconds=0.0, stats={}, engine0={}, engine1={}, calls=[],
                     trace=red, trace_calls=calls)
    got = per_layer(cfg, run.fam, bench, WORKLOAD, run)
    for name in ("device_idle_pct", "flash_roofline", "prefill_mfu_pct",
                 "decode_mfu_pct", "decode_roofline"):
        assert got[name] == result["metrics"][name]
        assert 0 < got[name]["value"] < 100
