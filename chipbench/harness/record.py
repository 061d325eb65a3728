"""What one run recorded, as the per-layer metric readers see it."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from chipbench.harness.trace import Event, Reduced


@dataclasses.dataclass
class Call:
    """One call into the engine, as the host made it."""
    t: float                 # host monotonic time of the call
    kind: str                # "prime", "decode" or "submit"
    prompt_len: int = 0      # prime: tokens prefilled
    contexts: Tuple[int, ...] = ()   # decode: position of each live row
    wait_s: float = 0.0      # submit: seconds the call took


@dataclasses.dataclass
class Run:
    cfg: Dict                # the configuration file
    fam: object              # its reference module (operation counts)
    peak: Dict               # the device's peaks
    seconds: float           # measured window length
    stats: Dict              # window statistics (harness.stats.window)
    engine0: Dict            # engine.metrics at the window's opening
    engine1: Dict            # ... and at its close
    calls: List[Call]        # engine calls dispatched inside the window
    trace: Optional[Reduced] = None
    trace_calls: List[Call] = dataclasses.field(default_factory=list)

    def delta(self, key: str) -> float:
        return float(self.engine1.get(key, 0)) - float(self.engine0.get(key, 0))

    def cost(self, call: Call):
        if call.kind == "prime":
            return self.fam.prefill_cost(self.cfg, call.prompt_len)
        return self.fam.decode_cost(self.cfg, call.contexts)

    def matched(self, kind: str) -> List[Tuple[Event, Call]]:
        """Each traced execution of a ``kind`` program with the host call
        that dispatched it: the latest such call at or before the start of
        the execution, each call used once."""
        if self.trace is None:
            return []
        calls = [c for c in self.trace_calls if c.kind == kind]
        lo, hi = self.trace.window
        out, j = [], 0
        for ev in self.trace.programs(kind):
            if ev.start < lo or ev.start + ev.dur > hi:
                continue            # cut by the trace's edges
            while j + 1 < len(calls) and calls[j + 1].t <= ev.start + 1e-3:
                j += 1
            if j < len(calls) and calls[j].t <= ev.start + 1e-3:
                if not out or out[-1][1] is not calls[j]:
                    out.append((ev, calls[j]))
        return out

    def roofline_s(self, flops: float, byts: float) -> float:
        return max(flops / self.peak["flops"], byts / self.peak["hbm_bytes_s"])
