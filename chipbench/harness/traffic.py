"""Open-loop request schedules from a traffic file and a seed.

A traffic file (``chipbench/traffic/<mix>.json``) holds only parameters:

- ``prompt`` / ``output``: a lognormal length law (``median``, ``sigma``),
  clipped to ``[min, max]``; a prompt length is then rounded up to the next
  of ``buckets`` (the engine compiles one prefill per prompt length).
- ``rate_rps``: the fixed arrival rate, by configuration name.
- ``lead_in_s``: how long before the window opens arrivals begin; see
  below.
- ``extends``: optional name of another traffic file whose keys this one
  overrides, so a new cell can bring its own rate in a file of its own.

The window holds the same work for every seed, in another order.  Its n =
``rate * seconds`` gaps are the exponential law's quantiles at
``(i + 0.5) / n``, scaled to span exactly the window, and its lengths the
lognormal laws' quantiles; each prompt length is paired with one output
length, the same pairs for every seed.  The seed draws one order of the
gaps and, apart from it, one order of the pairs.  So the gaps are
exchangeable, as Poisson's are: the count in any stretch of the window
varies as a Poisson count does (up to the correction for drawing without
replacement), while the window's requests, as a set, are the same for
every seed.

The schedule is periodic with the window's length: the lead-in replays
the window's last ``lead_in_s`` seconds one period earlier, with fresh
token ids.  With ``lead_in_s`` above the longest request's time in the
system, the tokens that the window's last requests emit after its close
are matched by those that the replayed ones emit after its opening, and
the window opens on the queue that it leaves at its close.  Token ids are
drawn from the seed, so no two requests share a prefix.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str, traffic_dir: Path = TRAFFIC_DIR) -> Dict:
    spec = json.loads((traffic_dir / f"{name}.json").read_text())
    base = spec.pop("extends", None)
    if base is None:
        return spec
    merged = load(base, traffic_dir)
    merged.update(spec)
    return merged


def rate_for(spec: Dict, config: str) -> float:
    rate = spec["rate_rps"]
    if isinstance(rate, dict):
        if config not in rate:
            raise KeyError(f"traffic has no rate for config {config!r}")
        rate = rate[config]
    return float(rate)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(law: Dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = float(law["median"]) * np.exp(float(law["sigma"]) * z)
    x = np.clip(np.ceil(x), law.get("min", 1), law["max"]).astype(np.int64)
    buckets = law.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x


def schedule(spec: Dict, config: str, seed: int, seconds: float,
             vocab_size: int) -> List[Dict]:
    """Requests ``{"i", "due_s", "prompt", "max_new_tokens"}``, with
    ``due_s`` relative to the opening of the measured window (negative in
    the lead-in), sorted by due time."""
    rate = rate_for(spec, config)
    lead = float(spec.get("lead_in_s", 0.0))
    n = max(1, int(round(rate * seconds)))
    # exponential quantiles, scaled so that the n gaps span the window
    gaps = -np.log1p(-_quantiles(n))
    gaps *= seconds / gaps.sum()
    # each prompt length is paired with one output length, the same pairs
    # for every seed (the pairing is drawn once, from a constant)
    prompts = _lognormal(spec["prompt"], n)
    outputs = _lognormal(spec["output"], n)[
        np.random.default_rng(0).permutation(n)]
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    sizes = rng.permutation(n)
    due = np.cumsum(gaps) - gaps[0]
    # the window's requests, and its tail replayed a period earlier
    slots = [(float(due[j] - p * seconds), j)
             for p in range(int(math.ceil(lead / seconds)), 0, -1)
             for j in range(n) if due[j] - p * seconds >= -lead]
    slots += [(float(due[j]), j) for j in range(n)]
    reqs = []
    for i, (t, j) in enumerate(slots):
        reqs.append({
            "i": i, "due_s": t,
            "prompt": rng.integers(1, vocab_size,
                                   int(prompts[sizes[j]])).tolist(),
            "max_new_tokens": int(outputs[sizes[j]])})
    return reqs


def warmup_prompts(spec: Dict, seed: int, vocab_size: int) -> List[List[int]]:
    """One prompt per bucket, drawn apart from the served ones (a served
    prompt that repeated one would take the prefix-cache path)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(1, vocab_size, int(n)).tolist()
            for n in sorted(spec["prompt"]["buckets"])]
