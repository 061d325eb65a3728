"""The harness end to end at toy widths on the CPU, with the look for a chip
skipped: a sound run is correct, and each fault a serving cell can have,
planted underneath the timed path, turns ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import time

import jax
import pytest

from chipbench.harness import bench
from repro.serving import engine as engine_mod

import tiny

CELLS = [("internlm2-1chip.chat", "internlm2-20b-1chip"),
         ("internlm2-1chip.longprompt", "internlm2-20b-1chip")]
SEED = 2**31 + 12345            # more than 32 signed bits hold


def _run(workload, config, seed=SEED):
    return bench.run_cell(tiny.bench(), workload, seed, 3.0, False,
                          jax.devices()[:1], time.monotonic(),
                          log=lambda s: None, cfg=tiny.config(config),
                          spec=tiny.traffic("chat"), peak=tiny.PEAK)


@pytest.mark.parametrize("workload,config", CELLS)
def test_sound_run_is_correct(workload, config):
    res = _run(workload, config)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in tiny.bench()["end_to_end"]
        if workload in m.get("workloads", [workload])}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"


def _frozen_state(build):
    """A decode step that returns its cache (the KV pages) unchanged: the
    step's writes are lost."""
    def built(*a, **kw):
        step = build(*a, **kw)

        def frozen(params, cache, *rest):
            _, logits = step(params, cache, *rest)
            return cache, logits
        return frozen
    return built


def _altered_token(emit):
    """The fourth token of every request replaced where it is produced."""
    def altered(self, r, tok):
        if len(r.generated) == 3:
            tok = (int(tok) + 1) % self.cfg.vocab_size
        return emit(self, r, tok)
    return altered


@pytest.mark.parametrize("workload,config", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_fault_is_not_correct(monkeypatch, workload, config, fault):
    if fault == "state_unchanged":
        for name in ("build_decode_step", "build_decode_step_paged"):
            monkeypatch.setattr(engine_mod, name,
                                _frozen_state(getattr(engine_mod, name)))
    else:
        monkeypatch.setattr(engine_mod.ServingEngine, "_emit",
                            _altered_token(engine_mod.ServingEngine._emit))
    res = _run(workload, config)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("workload,config", CELLS)
def test_control_reads_above_the_limit(workload, config):
    """The control (the reference at float8 in the program's place) on the
    same prompts and served tokens reads a wider gap than the limit."""
    cfg = tiny.config(config)
    spec = tiny.traffic("chat")
    setup = bench.Setup(cfg, spec, SEED)
    w = bench.serve_window(setup, config, SEED, 3.0, bench.CompileCounter())
    setup.plane.stop()
    nums = bench.check_numbers(setup, w["records"], SEED, control=True)
    limit = cfg["check"]["max_logit_gap"]
    assert nums["max_logit_gap"] <= limit
    assert nums["control_max_logit_gap"] > limit, nums
