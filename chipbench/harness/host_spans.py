"""The serving engine's host spans in a profiler trace, and the device's
idle time put down to them.

The engine opens a named ``TraceAnnotation`` for each of its phases
(``engine.step``, ``engine.prime``, ``engine.decode`` ...).  The profiler
records them on the host planes, on the clock the device planes share;
:func:`host_spans` ties them to the host's ``time.monotonic`` through the
benchmark's ``chipbench.sync`` annotation, as ``trace.reduce`` ties the
device's events.  The engine's driver thread is the line that holds
``engine.step`` (several host lines share a thread name, so the name does
not say which).

:func:`idle_split` splits the device's idle time in the traced window by
the innermost driver span open at each idle instant.  Idle under
``engine.park`` is idle for want of work; idle under any other engine span
is idle the host caused (:func:`idle_host_pct`).  The profiler records no
span that opened before it started or closes after it stopped, so idle time
before the driver's first recorded step or park, or after its last, with no
recorded span open, reads as the window's edge; other idle time with no
driver span open reads as unspanned.  A program without these spans yields
none, and every idle instant reads as the window's edge.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from chipbench.harness import trace as tr

PREFIX = "engine."
PARK = "engine.park"
UNSPANNED = "unspanned"
EDGE = "window edge"
#: idle under ``engine.step`` before its first inner span: ``step`` opens
#: its span, then takes the engine's lock, then admits, so this is the
#: driver waiting for the lock (or for the interpreter lock after it)
STEP_LOCK = "engine.step, taking the lock"
#: the driver's phases finer than ``engine.step``
FINER = ("engine.prime", "engine.prepare", "engine.decode", "engine.sample",
         "engine.emit")


@dataclasses.dataclass
class Span:
    name: str               # with any ``#k=v#`` arguments stripped
    line: str               # "<plane>:<line index>"
    start: float            # seconds, host monotonic clock
    dur: float              # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


def host_spans(path: str, sync_mono: float) -> List[Span]:
    """Every ``engine.*`` event of the host planes, by start.
    ``sync_mono``: the host monotonic time at which the ``SYNC`` annotation
    opened."""
    from jax.profiler import ProfileData

    sync_ns, raw = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for name, start, dur in tr._events(line):
                if name == tr.SYNC:
                    sync_ns = start
                elif name.startswith(PREFIX):
                    raw.append((name.split("#", 1)[0], f"{plane.name}:{i}",
                                start, dur))
    if sync_ns is None:
        raise RuntimeError(f"trace has no {tr.SYNC} annotation")
    return sorted((Span(n, ln, sync_mono + (s - sync_ns) / 1e9, d / 1e9)
                   for n, ln, s, d in raw), key=lambda s: s.start)


def driver(spans: List[Span]) -> List[Span]:
    """The spans of the line that holds the most ``engine.step``."""
    steps: Dict[str, int] = {}
    for s in spans:
        if s.name == "engine.step":
            steps[s.line] = steps.get(s.line, 0) + 1
    if not steps:
        return []
    line = max(steps, key=steps.get)
    return [s for s in spans if s.line == line]


def idle_intervals(red: tr.Reduced) -> List[Tuple[float, float]]:
    """The traced window less the union of the device's ops (its programs
    where the trace has no ops), as ``trace.reduce`` counts busy time."""
    lo, hi = red.window
    busy = []
    for evs in (red.ops, red.modules):
        busy = [(max(e.start, lo), min(e.start + e.dur, hi)) for e in evs
                if e.start + e.dur > lo and e.start < hi]
        if busy:
            break
    out, t = [], lo
    for s, e in sorted(busy):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_split(red: tr.Reduced, spans: List[Span]) -> Dict[str, float]:
    """Device-idle seconds of the window by the innermost driver span open
    at each idle instant (``EDGE`` or ``UNSPANNED`` where none is, and
    ``STEP_LOCK`` for a step none of whose inner spans has opened yet)."""
    drv = driver(spans)
    top = [s for s in drv if s.name in ("engine.step", PARK)]
    first = min((s.start for s in top), default=red.window[1])
    last = max((s.end for s in top), default=red.window[1])
    # (time, closes before opens, inner first, kind, index)
    ev = []
    for a, b in idle_intervals(red):
        ev.append((a, 1, 0.0, "idle", 0))
        ev.append((b, 0, 0.0, "idle", 0))
    for i, s in enumerate(drv):
        if s.dur <= 0:
            continue
        ev.append((s.start, 1, -s.end, "span", i))
        ev.append((s.end, 0, -s.start, "span", i))
    ev.sort(key=lambda e: e[:3])
    out: Dict[str, float] = {}
    stack: List[int] = []
    entered = set()             # spans in which an inner span has opened
    idle, t_prev = False, red.window[0]
    for t, opens, _, kind, i in ev:
        if idle and t > t_prev:
            if stack:
                label = drv[stack[-1]].name
                if label == "engine.step" and stack[-1] not in entered:
                    label = STEP_LOCK
            else:
                label = EDGE if t <= first or t_prev >= last else UNSPANNED
            out[label] = out.get(label, 0.0) + (t - t_prev)
        t_prev = max(t_prev, t)
        if kind == "idle":
            idle = bool(opens)
        elif opens:
            if stack:
                entered.add(stack[-1])
            stack.append(i)
        else:
            stack.remove(i)
    return out


def _host_s(split: Dict[str, float]) -> float:
    return sum(v for k, v in split.items()
               if k not in (PARK, UNSPANNED, EDGE))


def idle_host_pct(red: tr.Reduced, spans: List[Span]) -> float:
    """The share of the window in which the device is idle while the driver
    is inside an engine span other than ``engine.park``."""
    return 100.0 * _host_s(idle_split(red, spans)) / red.window_s


def split_line(red: tr.Reduced, spans: List[Span]) -> str:
    """One log line: the window's idle time by driver span, and the share
    of it under a span finer than ``engine.step`` or under ``engine.park``."""
    split = idle_split(red, spans)
    idle = sum(split.values())
    covered = sum(v for k, v in split.items() if k in FINER + (PARK,))
    parts = ", ".join(f"{k} {v * 1e3:.2f} ms"
                      for k, v in sorted(split.items(), key=lambda kv: -kv[1]))
    return (f"device idle {idle * 1e3:.2f} ms of {red.window_s * 1e3:.2f} ms "
            f"({100.0 * idle / red.window_s:.3f}%): {parts}; idle_host_pct "
            f"{100.0 * _host_s(split) / red.window_s:.3f}; under a finer "
            f"span or park {100.0 * covered / idle if idle else 0.0:.2f}%")
