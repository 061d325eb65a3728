"""Reduction of a profiler trace (``.xplane.pb``) to device times.

What it reads, from the plane of the first TPU device:

- the line of XLA modules (one event per execution of a jitted program),
  classified by name: a name with ``prime`` is an admission prefill, one
  with ``decode`` a decode step;
- the line of XLA ops (one event per operation), whose union is the time
  the device was busy, and whose events with ``flash`` in their name are
  the flash-attention kernel;
- on the host planes, the benchmark's own ``chipbench.sync`` annotation,
  which ties the trace's clock to the host's ``time.monotonic``.

Everything is in seconds on the host's monotonic clock once tied.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SYNC = "chipbench.sync"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds, host monotonic clock
    dur: float              # seconds


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    modules: List[Event]
    ops: List[Event]
    busy_s: float

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def programs(self, word: str) -> List[Event]:
        return [e for e in self.modules if word in e.name.lower()]

    def kernels(self, word: str) -> List[Event]:
        """Ops whose own name (not their operands') holds ``word``."""
        return [e for e in self.ops
                if word in e.name.split(" = ", 1)[0].lower()]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(path: str, sync_mono: float, window: Tuple[float, float]
           ) -> Reduced:
    """``sync_mono``: the host monotonic time at which the ``SYNC``
    annotation opened; ``window``: the traced window on that clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    sync_ns = None
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0") and device is None:
            device = plane
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for name, start, _ in _events(line):
                    if name == SYNC:
                        sync_ns = start
    if device is None:
        raise RuntimeError("trace has no /device:TPU:0 plane")
    if sync_ns is None:
        raise RuntimeError(f"trace has no {SYNC} annotation")

    def to_host(ns: int) -> float:
        return sync_mono + (ns - sync_ns) / 1e9

    modules, ops = [], []
    for line in device.lines:
        lname = line.name.lower()
        if lname == "xla modules":
            dest = modules
        elif lname == "xla ops":
            dest = ops
        else:
            continue
        for name, start, dur in _events(line):
            dest.append(Event(name, to_host(start), dur / 1e9))
    lo, hi = window

    def clip(evs):
        out = []
        for e in evs:
            s, t = max(e.start, lo), min(e.start + e.dur, hi)
            if t > s:
                out.append((s, t))
        return out

    busy = _union(clip(ops) or clip(modules))
    return Reduced(window, sorted(modules, key=lambda e: e.start),
                   sorted(ops, key=lambda e: e.start), busy)


def _label(name: str) -> str:
    n = name.lower()
    if "prime" in n:
        return "prime"
    if "decode" in n:
        return "decode"
    return name.split("(")[0]


def op_label(name: str) -> str:
    """A short name for an op event, whose name is its HLO text:
    ``copy.87 copy bf16[8,4097,16,8,128]`` for
    ``%copy.87 = bf16[8,4097,16,8,128]{4,3,2,1,0:T(8,128)} copy(...)``; a
    tuple-valued result is written ``(...)``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "(...)", rhs[i + 1:].lstrip()
    else:
        shape, _, rest = rhs.partition(" ")
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{lhs.lstrip('%')} {rest.split('(', 1)[0]} {shape}"


def _leaves(evs: List[Event]) -> List[Event]:
    """The ops that contain no other op (a ``while`` contains its body's)."""
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.start + e.dur:
            out.append(e)
    return out


def breakdown(red: Reduced, top: int = 10) -> Dict:
    """The device ops that took most time (summed by op, leaf ops only),
    and the longest idle gaps, each named by the programs that ran on
    either side of it."""
    by_op: Dict[str, float] = {}
    lo, hi = red.window
    for e in _leaves(red.ops):
        if lo <= e.start < hi:
            key = op_label(e.name)
            by_op[key] = by_op.get(key, 0.0) + e.dur
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    mods = [e for e in red.modules if e.start + e.dur > lo and e.start < hi]
    gaps = []
    prev_end, prev_name = lo, "window open"
    for e in mods:
        if e.start > prev_end:
            gaps.append((f"{_label(prev_name)} -> {_label(e.name)}",
                         e.start - prev_end))
        if e.start + e.dur > prev_end:
            prev_end, prev_name = e.start + e.dur, e.name
    if hi > prev_end:
        gaps.append((f"{_label(prev_name)} -> window close", hi - prev_end))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def summary(path: str, limit: int = 40) -> Dict:
    """Planes, lines and the commonest event names: for looking at a trace
    by hand before writing code against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = {}
            for name, _, _ in _events(line):
                names[name] = names.get(name, 0) + 1
            lines[line.name] = sorted(names.items(), key=lambda kv: -kv[1])[
                :limit]
        out[plane.name] = lines
    return out
