"""The system under test: one serving plane in this process.

Builds the program's ``ArchConfig`` from a configuration file, draws the
weights, and starts ``Orchestrator`` + ``LmServingAdapter`` (continuous-
batching ``ServingEngine``) behind a ``ControlPlaneGateway`` on loopback.
The load generator reaches it from its own process, as a user would.

It also records, from outside the program, each call into the engine's
jitted admission (``_prime``) and decode (``_decode``) programs: the host
time it was made and the shapes it carried.  The per-layer metrics read
these records; a later program that renames the attributes leaves them
empty and those metrics silent.
"""
from __future__ import annotations

import importlib
import time
from typing import Dict, List

import jax

from chipbench.harness import paths

paths.add_src()

from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,  # noqa: E402
                                RecurrentConfig, RWKVConfig)
from repro.core import Orchestrator, TaskRequest  # noqa: E402
from repro.gateway import ControlPlaneGateway  # noqa: E402
from repro.models import model_specs  # noqa: E402
from repro.models.common import abstract_params  # noqa: E402
from repro.substrates.lm_serving import LmServingAdapter  # noqa: E402

#: scheduler workers and adapter slots.  Each ``LmServingAdapter.invoke``
#: holds a worker for the whole generation, so this has to cover every
#: request in flight: above the most that the page pool can reserve for in
#: these mixes (its pages over the fewest a request reserves).
WORKERS = 512

_NESTED = {"moe": MoEConfig, "mla": MLAConfig, "recurrent": RecurrentConfig,
           "rwkv": RWKVConfig}


def arch_config(cfgj: Dict) -> ArchConfig:
    """The program's config from the file's ``arch`` object, key for key."""
    kw = dict(cfgj["arch"])
    for key, cls in _NESTED.items():
        if isinstance(kw.get(key), dict):
            kw[key] = cls(**kw[key])
    if "block_pattern" in kw:
        kw["block_pattern"] = tuple(kw["block_pattern"])
    return ArchConfig(**kw)


def family(cfgj: Dict):
    """The configuration's reference module, ``chipbench.reference.<x>``:
    its plain forward pass, weight laws and operation counts."""
    return importlib.import_module(f"chipbench.reference.{cfgj['reference']}")


def draw_weights(cfgj: Dict, arch: ArchConfig, seed: int):
    """All weights in one jitted call on the device, in the program's
    layout and served dtype, each leaf from its own key off ``seed``."""
    fam = family(cfgj)
    shapes = abstract_params(model_specs(arch))
    return jax.block_until_ready(fam.draw(shapes, seed))


def task(task_id: str, prompt, max_new: int) -> TaskRequest:
    return TaskRequest(task_id=task_id, function="generate",
                       input_modality="tokens", output_modality="tokens",
                       payload={"prompt": [int(t) for t in prompt],
                                "max_new_tokens": int(max_new)})


class CallLog:
    """Host-side records of calls into the engine: ``(t, kind, arg)``, with
    ``arg`` the prompt length of a prime, the device array of row positions
    of a decode step (read back only after the window), and the seconds a
    ``submit`` took (the wait for the engine's lock is most of it)."""

    def __init__(self):
        self.calls: List = []

    def wrap(self, engine) -> None:
        for attr, kind in (("_prime", "prime"), ("_decode", "decode")):
            fn = getattr(engine, attr, None)
            if fn is None:
                continue
            setattr(engine, attr, self._wrapped(fn, kind))
        submit, calls = engine.submit, self.calls

        def timed_submit(r):
            t = time.monotonic()
            try:
                return submit(r)
            finally:
                calls.append((t, "submit", time.monotonic() - t))
        engine.submit = timed_submit

    def _wrapped(self, fn, kind):
        calls = self.calls

        def call(params, batch_or_cache, *args):
            if kind == "prime":
                arg = int(batch_or_cache["tokens"].shape[1])
            else:
                arg = args[1]                  # (B,) positions, on device
            t = time.monotonic()
            out = fn(params, batch_or_cache, *args)
            calls.append((t, kind, arg))
            return out

        return call

    def between(self, t0: float, t1: float) -> List:
        return [c for c in self.calls if t0 <= c[0] < t1]


class Plane:
    """Orchestrator + serving adapter + gateway for one configuration."""

    def __init__(self, cfgj: Dict, arch: ArchConfig, params):
        eng = cfgj["engine"]
        self.orch = Orchestrator(plane="chipbench")
        # admission pricing is off the path (no latency budgets), so the
        # cost model needs no calibration request
        self.adapter = LmServingAdapter(
            arch, params=params, batch_size=eng["batch_size"],
            max_seq=eng["max_seq"], paged=eng["paged"],
            page_size=eng["page_size"],
            prefix_sharing=eng["prefix_sharing"], calibrate=False,
            max_concurrent=WORKERS)
        self.orch.register(self.adapter)
        self.gateway = ControlPlaneGateway(self.orch, plane="chipbench",
                                           workers=WORKERS).start()
        self.log = CallLog()

    @property
    def url(self) -> str:
        return self.gateway.url

    @property
    def engine(self):
        return self.adapter.engine

    def serve(self, task_id: str, prompt, max_new: int):
        res, trace = self.orch.submit(task(task_id, prompt, max_new))
        if res.status != "completed":
            raise RuntimeError(f"warm-up {task_id}: {res.status} "
                               f"({trace.rejected_reason})")
        return res

    def warm(self, prompts) -> None:
        """Serve each warm-up prompt alone, so that each prompt length
        compiles its prime and each alone reaches its own decode width;
        then record the engine's calls from here on."""
        for i, p in enumerate(prompts):
            self.serve(f"warm-{i}", p, 2)
        self.log.wrap(self.engine)

    def stop(self) -> None:
        self.gateway.stop()
        self.adapter.close()
        if self.adapter.engine is not None:
            self.adapter.engine.flush()
            self.adapter.engine.params = None
        self.adapter.params = None

