"""One cell, one run: set-up, the measured window, the output check.

The flow of :func:`run`:

1. set-up (``setup_s``): compile cache, the weights drawn on the device
   from the seed, the plane (gateway, scheduler, orchestrator, adapter,
   engine), one warm-up request per prompt bucket served alone (each prime
   length and each decode width the cell reaches), the load generator
   started and ready;
2. the lead-in and the measured window: the generator plays the schedule
   open loop; the process counts compilations, snapshots the engine's
   counters at the window's edges, and with ``trace`` profiles a few
   seconds in the middle of the window;
3. the drain: every request due in the window gets its answer or, a bounded
   time after the close, counts as failed;
4. the device's peak memory is read, the program's state freed, and the
   output check runs on a sample of the finished requests.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench.harness import paths, stats, traffic

LOADGEN = paths.BENCH / "harness" / "loadgen.py"
#: how long after the window's close an answer may still come
DRAIN_S = 60.0
#: the length of the traced sub-window, centred in the measured window
TRACE_S = 4.0
#: a directory to copy each run's raw trace into (``tools/record_trace.py``)
KEEP_TRACE: Optional[str] = None


def load_benchmark() -> Dict:
    return json.loads((paths.ROOT / "BENCHMARK.json").read_text())


def load_config(bench: Dict, name: str) -> Dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((paths.ROOT / entry["file"]).read_text())


class CompileCounter:
    """Times at which JAX traced or compiled a program in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.times: List[tuple] = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.times.append((time.monotonic(), name.rsplit("/", 1)[-1]))

    def between(self, t0: float, t1: float) -> Dict[str, int]:
        out = {"traces": 0, "compiles": 0}
        for t, name in self.times:
            if t0 <= t < t1:
                out["compiles" if "backend" in name else "traces"] += 1
        return out


class Setup:
    """Everything that lives from set-up to the output check."""

    def __init__(self, cfg: Dict, spec: Dict, seed: int):
        from chipbench.harness import program

        self.cfg, self.spec = cfg, spec
        self.arch = program.arch_config(cfg)
        self.fam = program.family(cfg)
        #: host clock at the end of each phase of set-up
        self.marks = {"start": time.monotonic()}
        self.weights = program.draw_weights(cfg, self.arch, seed)
        self.marks["weights"] = time.monotonic()
        self.plane = program.Plane(cfg, self.arch, self.weights)
        self.marks["plane"] = time.monotonic()
        self.plane.warm(traffic.warmup_prompts(
            spec, seed, self.arch.vocab_size))
        self.marks["warm-up"] = time.monotonic()

    def set_weights(self, seed: int) -> None:
        """New weights in place of the old (the old freed first)."""
        from chipbench.harness import program

        self.plane.engine.params = None
        self.plane.adapter.params = None
        self.weights = None
        self.weights = program.draw_weights(self.cfg, self.arch, seed)
        self.plane.engine.params = self.weights


def _start_loadgen(reqs, seconds: float, url: str, tmp: str):
    path = os.path.join(tmp, "schedule.json")
    with open(path, "w") as f:
        json.dump({"requests": reqs, "timeout_s": 600.0,
                   "drain_until_s": seconds + DRAIN_S}, f)
    child = subprocess.Popen([sys.executable, str(LOADGEN), path, url],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             text=True)
    line = child.stdout.readline().strip()
    if line != "ready":
        child.kill()
        child.wait()
        raise RuntimeError(f"load generator did not start: {line!r}")
    return child


def _keep_trace(xp: str, t_sync: float, t_stop: float, trace_calls) -> None:
    """The raw trace, its tie to the host clock and the engine calls it
    covers, as the trace reduction's test reads them back."""
    import dataclasses

    from chipbench.harness import trace as tr

    os.makedirs(KEEP_TRACE, exist_ok=True)
    shutil.copy(xp, os.path.join(KEEP_TRACE, "trace.xplane.pb"))
    with open(os.path.join(KEEP_TRACE, "sync.json"), "w") as f:
        json.dump({"sync": t_sync, "window": [t_sync, t_stop],
                   "calls": [dataclasses.asdict(c) for c in trace_calls]}, f)
    with open(os.path.join(KEEP_TRACE, "summary.json"), "w") as f:
        json.dump(tr.summary(xp), f, indent=1)


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.5))


def serve_window(setup: Setup, config: str, seed: int, seconds: float,
                 compiles: CompileCounter, trace: bool = False,
                 rate: Optional[float] = None, on_ready=None) -> Dict:
    """Play one schedule against the plane; returns what the run saw."""
    from chipbench.harness import record
    from chipbench.harness import trace as tr

    spec = dict(setup.spec)
    if rate is not None:
        spec["rate_rps"] = rate
    reqs = traffic.schedule(spec, config, seed, seconds,
                            setup.arch.vocab_size)
    lead = float(spec.get("lead_in_s", 0.0))
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    engine = setup.plane.engine
    child = None
    try:
        child = _start_loadgen(reqs, seconds, setup.plane.url, tmp)
        if on_ready is not None:
            on_ready()
        t0 = time.monotonic() + lead + 0.3
        child.stdin.write(f"{t0!r}\n")
        child.stdin.flush()
        _sleep_until(t0)
        engine0 = dict(engine.metrics)
        traced = None
        if trace:
            import jax

            lo = t0 + seconds / 2 - TRACE_S / 2
            _sleep_until(lo)
            trace_dir = os.path.join(tmp, "trace")
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(tr.SYNC):
                t_sync = time.monotonic()
            _sleep_until(t_sync + TRACE_S)
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            traced = (trace_dir, t_sync, t_stop)
        t1 = t0 + seconds
        _sleep_until(t1)
        engine1 = dict(engine.metrics)
        in_window = compiles.between(t0, t1)
        out, _ = child.communicate(timeout=DRAIN_S + seconds + 120)
        recs = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
        if not recs or not recs[-1].get("done"):
            raise RuntimeError("load generator ended without its records")
        by_i = {r["i"]: r for r in recs[:-1]}
        records = []
        for q in reqs:
            r = dict(by_i.get(q["i"], {"status": "unfinished"}))
            r.update(i=q["i"], due_s=q["due_s"], prompt=q["prompt"],
                     max_new_tokens=q["max_new_tokens"])
            records.append(r)

        def calls(lo_, hi_):
            out_ = []
            for t, kind, arg in setup.plane.log.between(lo_, hi_):
                if kind == "prime":
                    out_.append(record.Call(t, kind, prompt_len=arg))
                elif kind == "submit":
                    out_.append(record.Call(t, kind, wait_s=arg))
                else:
                    pos = np.asarray(arg)
                    out_.append(record.Call(
                        t, kind, contexts=tuple(int(p) for p in pos if p > 0)))
            return out_

        reduced, trace_calls = None, []
        if traced is not None:
            trace_dir, t_sync, t_stop = traced
            xp = tr.find_xplane(trace_dir)
            if xp is not None:
                trace_calls = calls(t_sync - 1.0, t_stop)
                if KEEP_TRACE:
                    _keep_trace(xp, t_sync, t_stop, trace_calls)
                reduced = tr.reduce(xp, t_sync, (t_sync, t_stop))
        st = stats.window(records, seconds)
        return {"records": records, "stats": st, "engine0": engine0,
                "engine1": engine1, "calls": calls(t0, t1),
                "in_window": in_window, "trace": reduced,
                "trace_calls": trace_calls,
                "t0": t0, "t1": t1}
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        shutil.rmtree(tmp, ignore_errors=True)


def check_numbers(setup: Setup, records: List[Dict], seed: int,
                  control: bool = False) -> Dict:
    """The numbers compared: every finished request's token count against
    what it asked, and the widest logit gap of a sample of them."""
    from chipbench.harness import check

    chk = setup.cfg["check"]
    vocab = setup.arch.vocab_size
    miscounted = sum(1 for r in records if stats.ok(r) and (
        len(r["tokens"]) != r["max_new_tokens"]
        or not all(0 <= t < vocab for t in r["tokens"])))
    picked = check.sample(records, seed, chk["sample_requests"])
    seqs = [(list(r["prompt"]) + list(r["tokens"]), len(r["prompt"]))
            for r in picked]
    g = check.gaps(setup.fam, setup.cfg, setup.weights, seqs,
                   setup.cfg["engine"]["max_seq"], control=control)
    out = {"miscounted": miscounted, "sampled": len(picked),
           "sampled_tokens": int(sum(len(x["served"]) for x in g)),
           "max_logit_gap": float(max((x["served"].max() for x in g),
                                      default=float("nan")))}
    if control:
        out["control_max_logit_gap"] = float(max(
            (x["control"].max() for x in g), default=float("nan")))
    return out


def end_to_end(bench: Dict, workload: str, values: Dict) -> Dict:
    """The cell's end-to-end metrics, by name, from the window's values."""
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}


def per_layer(cfg: Dict, fam, bench: Dict, workload: str, run) -> Dict:
    out = {}
    for m in bench["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(bench: Dict, workload: str, seed: int, seconds: float,
             trace: bool, devs, t_start: float, log=print,
             cfg: Optional[Dict] = None, spec: Optional[Dict] = None,
             peak: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result line's object.  ``cfg``,
    ``spec`` and ``peak`` stand in for the cell's files and the device's
    peaks in the harness's own tests."""
    from chipbench.harness import device, record

    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = cfg or load_config(bench, cell["config"])
    spec = spec or traffic.load(cell["traffic"])
    info = device.info(devs)
    peak = peak or device.peaks(info["kind"])
    compiles = CompileCounter()
    setup = Setup(cfg, spec, seed)
    t_setup = {}
    w = serve_window(setup, cell["config"], seed, seconds, compiles,
                     trace=trace,
                     on_ready=lambda: t_setup.setdefault(
                         "s", time.monotonic() - t_start))
    st = w["stats"]
    m = setup.marks
    log(f"chipbench set-up: {t_setup['s']:.3f} s (to set-up start "
        f"{m['start'] - t_start:.3f}, weights "
        f"{m['weights'] - m['start']:.3f}, plane "
        f"{m['plane'] - m['weights']:.3f}, warm-up "
        f"{m['warm-up'] - m['plane']:.3f}); {cell['config']} "
        f"{setup.fam.param_count(cfg)} params")
    log(f"chipbench window: {workload} seed {seed} rate "
        f"{traffic.rate_for(spec, cell['config'])} req/s, {seconds} s: "
        f"attempted {st['attempted']} failed {st['failed']}; ttft p50 "
        f"{st['ttft_p50_ms']:.1f} p90 {st['ttft_p90_ms']:.1f} ms "
        f"(n={st['n_ttft']}); latency p50 {st['latency_p50_ms']:.1f} p90 "
        f"{st['latency_p90_ms']:.1f} ms; tpot p50 "
        f"{st['tpot_p50_ms']:.2f} ms (n={st['n_tpot']}); errors "
        f"{st['errors']}")
    log(f"chipbench generator lateness: p50 {st['late_p50_ms']:.2f} ms, "
        f"max {st['late_max_ms']:.2f} ms")
    log(f"chipbench compilations inside the window: {w['in_window']}")
    info = device.info(devs)                      # the peak, before the check
    setup.plane.stop()
    setup.plane = None
    run = record.Run(cfg=cfg, fam=setup.fam, peak=peak, seconds=seconds,
                     stats=st, engine0=w["engine0"], engine1=w["engine1"],
                     calls=w["calls"], trace=w["trace"],
                     trace_calls=w["trace_calls"])
    if trace:
        metrics = per_layer(cfg, setup.fam, bench, workload, run)
        if w["trace"] is not None:
            info["busy_s"] = w["trace"].busy_s
            info["window_s"] = w["trace"].window_s
    else:
        metrics = end_to_end(bench, workload, dict(st, setup_s=t_setup["s"]))
    t_chk = time.monotonic()
    nums = check_numbers(setup, w["records"], seed)
    log(f"chipbench check: sampled {nums['sampled']} requests, "
        f"{nums['sampled_tokens']} served tokens, in "
        f"{time.monotonic() - t_chk:.1f} s")
    limits = {"max_logit_gap": cfg["check"]["max_logit_gap"],
              "miscounted": 0}
    compared = {k: [nums[k], limits[k]] for k in limits}
    correct = (nums["sampled"] > 0 and nums["miscounted"] == 0
               and nums["max_logit_gap"] <= limits["max_logit_gap"])
    result = {"correct": bool(correct), "attempted": st["attempted"],
              "failed": st["failed"], "metrics": metrics, "device": info}
    if trace and w["trace"] is not None:
        from chipbench.harness import trace as tr

        result["breakdown"] = tr.breakdown(w["trace"])
    result["compared"] = compared
    return result
