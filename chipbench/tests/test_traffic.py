"""Schedules from the traffic files: the same work for every seed, in another
order; lengths inside the buckets and the engine's ``max_seq``."""
import json

import numpy as np
import pytest

from chipbench.harness import paths, stats, traffic

MIXES = [("chat", "internlm2-20b-1chip"),
         ("longprompt", "internlm2-20b-1chip")]


def _bench():
    return json.loads((paths.ROOT / "BENCHMARK.json").read_text())


def test_every_cell_has_a_rate():
    b = _bench()
    for w in b["workloads"]:
        spec = traffic.load(w["traffic"])
        assert traffic.rate_for(spec, w["config"]) > 0


@pytest.mark.parametrize("mix,config", MIXES)
def test_same_work_every_seed(mix, config):
    spec = traffic.load(mix)
    cfg = json.loads((paths.BENCH / "configs" / f"{config}.json").read_text())
    max_seq = cfg["engine"]["max_seq"]
    seconds = 45.0
    runs = [traffic.schedule(spec, config, seed, seconds, 1000)
            for seed in (1, 2**31 + 7, 3 * 2**32 + 11)]
    keys = []
    for reqs in runs:
        win = stats.in_window(reqs, seconds)
        lens = sorted((len(r["prompt"]), r["max_new_tokens"]) for r in win)
        gaps = np.diff([r["due_s"] for r in win] + [seconds + win[0]["due_s"]])
        keys.append((lens, sorted(np.round(gaps, 9))))
        assert [r["i"] for r in reqs] == list(range(len(reqs)))
        assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
        for r in reqs:
            assert len(r["prompt"]) in spec["prompt"]["buckets"]
            assert len(r["prompt"]) + r["max_new_tokens"] <= max_seq
    assert keys[0] == keys[1] == keys[2]
    orders = [[len(r["prompt"]) for r in stats.in_window(reqs, seconds)]
              for reqs in runs]
    assert orders[0] != orders[1]


@pytest.mark.parametrize("seconds", [45.0, 10.0])
def test_lead_in_replays_the_window_tail(seconds):
    """The lead-in is the window's last ``lead_in_s`` seconds, one period
    (or more, for a short window) earlier, with fresh token ids."""
    spec = traffic.load("chat")
    lead = spec["lead_in_s"]
    reqs = traffic.schedule(spec, "internlm2-20b-1chip", 9, seconds, 1000)
    win = stats.in_window(reqs, seconds)
    by_due = {round(r["due_s"], 9): r for r in win}
    pre = [r for r in reqs if r["due_s"] < 0]
    assert pre and all(r["due_s"] >= -lead for r in pre)
    want = [r for k in range(1, int(np.ceil(lead / seconds)) + 1)
            for r in win if r["due_s"] - k * seconds >= -lead]
    assert len(pre) == len(want)
    for r in pre:
        t = r["due_s"]
        while t < 0:
            t += seconds
        twin = by_due[round(t, 9)]
        assert twin["max_new_tokens"] == r["max_new_tokens"]
        assert len(twin["prompt"]) == len(r["prompt"])
        assert twin["prompt"] != r["prompt"]


def test_seed_gives_same_schedule():
    spec = traffic.load("chat")
    a = traffic.schedule(spec, "internlm2-20b-1chip", 5, 10.0, 1000)
    b = traffic.schedule(spec, "internlm2-20b-1chip", 5, 10.0, 1000)
    assert a == b


def test_tokens_in_window():
    r = {"tokens": [1] * 11, "done_s": 3.0, "total_ms": 1200.0,
         "ttft_ms": 200.0}
    # first token at 2.0 s, ten more spread over (2.0, 3.0]
    assert stats.tokens_in(r, 0.0, 10.0) == pytest.approx(11)
    assert stats.tokens_in(r, 0.0, 2.5) == pytest.approx(6)
    assert stats.tokens_in(r, 2.5, 10.0) == pytest.approx(5)
