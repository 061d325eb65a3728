"""Window statistics from the load generator's records (host clocks).

Per request due in the window:

- latency = the client's time from the request's due time to its answer
  (the gateway answers once, with every token);
- TTFT = the engine's ``ttft_ms`` (submit to first token) plus the control
  path outside the adapter: the client's latency minus the adapter's
  ``output.total_ms``.  That control path counts the way back too, since
  the wire carries no first-token stamp yet;
- TPOT = ``(output.total_ms - ttft_ms) / (n - 1)``: the mean gap between
  output tokens (requests of one token have none).

``out_tok_s`` counts the output tokens emitted inside the window, by any
request (lead-in requests and those that finish in the drain included),
over the window's length.  The client sees a request's first token at
``done - (total_ms - ttft_ms)`` and its last at ``done``; the tokens
between are spread evenly over that span.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(records: List[Dict], seconds: float) -> List[Dict]:
    return [r for r in records if 0.0 <= r["due_s"] < seconds]


def ttft_ms(r: Dict) -> float:
    return r["ttft_ms"] + (r["latency_ms"] - r["total_ms"])


def tpot_ms(r: Dict) -> float:
    return (r["total_ms"] - r["ttft_ms"]) / (len(r["tokens"]) - 1)


def tokens_in(r: Dict, lo: float, hi: float) -> float:
    """Output tokens of a finished request emitted in ``[lo, hi)`` (seconds
    on the window's clock)."""
    n = len(r["tokens"])
    last = r["done_s"]
    first = last - (r["total_ms"] - r["ttft_ms"]) / 1e3
    got = 1.0 if lo <= first < hi else 0.0
    if n > 1:
        span = last - first
        if span <= 0:
            return float(n) if lo <= last < hi else 0.0
        got += (n - 1) * max(0.0, min(hi, last) - max(lo, first)) / span
    return got


def ok(r: Dict) -> bool:
    return r.get("status") == "completed" and r.get("total_ms") is not None


def window(records: List[Dict], seconds: float) -> Dict:
    win = in_window(records, seconds)
    done = [r for r in win if ok(r)]
    ttft = [ttft_ms(r) for r in done]
    tpot = [tpot_ms(r) for r in done if len(r["tokens"]) > 1]
    ctl = [r["latency_ms"] - r["total_ms"] for r in done]
    lat = [r["latency_ms"] for r in done]
    out_tok = sum(tokens_in(r, 0.0, seconds) for r in records if ok(r))
    late = [r["late_ms"] for r in records if "late_ms" in r]
    nan = math.nan
    return {
        "attempted": len(win), "failed": len(win) - len(done),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
        "ttft_p50_ms": pct(ttft, 50) if ttft else nan,
        "ttft_p90_ms": pct(ttft, 90) if ttft else nan,
        "tpot_p50_ms": pct(tpot, 50) if tpot else nan,
        "tpot_p90_ms": pct(tpot, 90) if tpot else nan,
        "ctl_ms_p50": pct(ctl, 50) if ctl else nan,
        "latency_p50_ms": pct(lat, 50) if lat else nan,
        "latency_p90_ms": pct(lat, 90) if lat else nan,
        "out_tok_s": out_tok / seconds,
        "late_p50_ms": pct(late, 50) if late else nan,
        "late_max_ms": max(late) if late else nan,
        "errors": sorted({r.get("error", r.get("status"))
                          for r in win if not ok(r)})[:5],
    }
