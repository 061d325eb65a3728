"""The engine's host spans in a trace, and the device's idle time put down
to them: the overlap arithmetic on hand-built intervals, a trace recorded
before the engine had spans, and a short trace recorded on one TPU v5e chip
with them (``tools/idle_split.py`` on ``internlm2-1chip.chat``, half a
second traced)."""
import json

import pytest

from chipbench.harness import host_spans as hs
from chipbench.harness import paths, record
from chipbench.harness import trace as tr
from chipbench.metrics import lock_wait_ms, queue_ms, step_host_ms

DATA = paths.BENCH / "tests" / "data"


def _red(window, busy):
    ops = [tr.Event("op", s, e - s) for s, e in busy]
    return tr.Reduced(window, [], ops, tr._union(busy))


def _spans(line, *spans):
    return [hs.Span(name, line, s, e - s) for name, s, e in spans]


DRIVER = _spans(
    "host:1",
    ("engine.step", 0.5, 7.0), ("engine.prepare", 0.5, 1.5),
    ("engine.decode", 1.5, 3.2), ("engine.sample", 3.2, 4.0),
    ("engine.emit", 4.0, 4.5), ("engine.park", 7.5, 9.5),
    ("engine.step", 9.6, 10.5), ("engine.admit", 9.8, 9.9),
    ("engine.emit", 9.95, 9.95))
#: a caller's line: its spans are not the driver's
CALLER = _spans("host:2", ("engine.submit", 0.0, 10.0),
                ("engine.submit.lock", 0.0, 10.0))


def test_idle_split_on_hand_built_intervals():
    red = _red((0.0, 10.0), [(1.0, 3.0), (5.0, 6.0)])
    split = hs.idle_split(red, sorted(DRIVER + CALLER,
                                      key=lambda s: s.start))
    want = {hs.EDGE: 0.5, "engine.prepare": 0.5, "engine.decode": 0.2,
            "engine.sample": 0.8, "engine.emit": 0.5,
            "engine.step": 0.5 + 1.0 + 0.1, hs.STEP_LOCK: 0.2,
            "engine.admit": 0.1, hs.UNSPANNED: 0.5 + 0.1, hs.PARK: 2.0}
    assert split.keys() == want.keys()
    for k, v in want.items():
        assert split[k] == pytest.approx(v), k
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s)
    assert hs.idle_host_pct(red, DRIVER + CALLER) == pytest.approx(39.0)
    line = hs.split_line(red, DRIVER)
    assert "idle_host_pct 39.000" in line
    # finer spans (2.0) and park (2.0) of 7.0 s idle
    assert f"{100 * 4.0 / 7.0:.2f}%" in line


def test_idle_after_the_last_recorded_step_is_the_edge():
    red = _red((0.0, 10.0), [(1.0, 3.0), (5.0, 6.0)])
    split = hs.idle_split(red, DRIVER[:-3])
    assert split[hs.EDGE] == pytest.approx(0.5 + 0.5)
    assert split[hs.UNSPANNED] == pytest.approx(0.5)


def test_the_driver_is_the_line_with_most_steps():
    other = _spans("host:3", ("engine.step", 2.0, 2.5))
    assert hs.driver(other + DRIVER) == DRIVER
    assert hs.driver(CALLER) == []


@pytest.fixture(scope="module")
def recorded_spans():
    d = DATA / "trace_spans"
    sync = json.loads((d / "sync.json").read_text())
    red = tr.reduce(str(d / "trace.xplane.pb"), sync["sync"],
                    tuple(sync["window"]))
    spans = hs.host_spans(str(d / "trace.xplane.pb"), sync["sync"])
    return (red, spans, json.loads((d / "result.json").read_text()),
            json.loads((d / "spans.json").read_text()))


def test_trace_without_spans_reads_all_edge():
    sync = json.loads((DATA / "trace" / "sync.json").read_text())
    path = str(DATA / "trace" / "trace.xplane.pb")
    red = tr.reduce(path, sync["sync"], tuple(sync["window"]))
    spans = hs.host_spans(path, sync["sync"])
    assert spans == []
    split = hs.idle_split(red, spans)
    assert list(split) == [hs.EDGE]
    assert split[hs.EDGE] == pytest.approx(red.window_s - red.busy_s)
    assert hs.idle_host_pct(red, spans) == 0.0


def _inside(inner, outers):
    return any(o.start <= inner.start and inner.end <= o.end for o in outers)


def test_recorded_driver_line_and_nesting(recorded_spans):
    red, spans, _, _ = recorded_spans
    drv = hs.driver(spans)
    names = {s.name for s in drv}
    assert {"engine.step", "engine.admit", "engine.prime", "engine.prepare",
            "engine.decode", "engine.sample", "engine.emit"} <= names
    steps = [s for s in drv if s.name == "engine.step"]
    admits = [s for s in drv if s.name == "engine.admit"]
    # a step open when the profiler started was not recorded: its inner
    # spans before the first recorded step have no parent in the trace
    lo, hi = steps[0].start, steps[-1].end
    for s in drv:
        if not lo <= s.start <= s.end <= hi:
            continue
        if s.name in ("engine.admit", "engine.prepare", "engine.decode",
                      "engine.sample"):
            assert _inside(s, steps), s
        elif s.name == "engine.prime":
            assert _inside(s, admits), s
    # the callers' spans are on lines of their own
    callers = {s.line for s in spans if s.name == "engine.submit.lock"}
    assert callers and drv[0].line not in callers


def test_recorded_idle_split(recorded_spans):
    red, spans, result, kept = recorded_spans
    split = hs.idle_split(red, spans)
    idle = red.window_s - red.busy_s
    assert sum(split.values()) == pytest.approx(idle)
    host = hs.idle_host_pct(red, spans)
    assert 0.0 < host <= result["metrics"]["device_idle_pct"]["value"]
    assert sum(split.values()) == pytest.approx(sum(kept["split_s"].values()))
    assert host == pytest.approx(kept["idle_host_pct"])


def test_recorded_counter_metrics(recorded_spans):
    _, _, result, _ = recorded_spans
    for name in ("lock_wait_ms", "queue_ms", "step_host_ms"):
        assert result["metrics"][name]["value"] > 0.0, name


def test_counter_readers_are_silent_without_the_counters():
    """A program without the engine's counters reports none of them."""
    run = record.Run(cfg=None, fam=None, peak=None, seconds=1.0, stats={},
                     engine0={"decode_steps": 3, "decode_ms": 9.0},
                     engine1={"decode_steps": 9, "decode_ms": 27.0},
                     calls=[])
    for reader in (lock_wait_ms, queue_ms, step_host_ms):
        assert reader.read(run) is None
    run.engine0.update(submits=2, lock_wait_ms=1.0, primes=1, queue_ms=5.0,
                       host_ms=4.0)
    run.engine1.update(submits=6, lock_wait_ms=9.0, primes=3, queue_ms=9.0,
                       host_ms=16.0)
    assert lock_wait_ms.read(run) == pytest.approx(2.0)
    assert queue_ms.read(run) == pytest.approx(2.0)
    assert step_host_ms.read(run) == pytest.approx(2.0)
