"""One serving pass through the control plane, with its correctness checks.

:func:`run` serves ``generate`` tasks for one config through the user's
entry points — ``Orchestrator.submit`` and ``ControlPlaneGateway`` +
``ControlPlaneClient`` — into :class:`~repro.substrates.LmServingAdapter`,
once on the slot-granular KV layout and once on the paged one, sharing one
set of weights.  Then it checks the model path's numerics directly: the
flash-kernel prefill against the jnp prefill, and one decode step through
the cache against a fresh prefill of the same tokens.

``chip_smoke.py`` runs it on one TPU chip at published widths; the tier-1
tests run it at ``reduced()`` size on the CPU.  Every failed check raises
:class:`SmokeCheckFailed`.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Orchestrator, TaskRequest
from repro.gateway import ControlPlaneClient, ControlPlaneGateway
from repro.models import (build_decode_step, build_prefill_step,
                          decode_cache, model_specs)
from repro.models.common import init_params
from repro.serving.cache_utils import extend_cache, write_slots
from repro.substrates.lm_serving import LmServingAdapter

#: Bound on ``||a - b|| / ||b||`` between two computations of the same
#: logits, by the config's compute dtype.
LOGIT_RTOL = {
    # bf16 keeps 8 significant bits (unit roundoff 2^-9).  Two bf16 paths
    # round different intermediates (attention probabilities, each layer's
    # activations, the logits themselves); at 8 layers they differ by about
    # 1.5% of the logits' norm (CPU, small widths).  5% leaves room for
    # depth and width, and a path computed in float8 (2^-4) would still
    # fail it.
    "bfloat16": 0.05,
    # float32 paths agree to ~1e-6 (CPU); 1e-4 fails any bf16 path by far.
    "float32": 1e-4,
}


class SmokeCheckFailed(RuntimeError):
    """A check of the serving smoke run did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _task(task_id: str, prompt: np.ndarray, max_new: int) -> TaskRequest:
    return TaskRequest(task_id=task_id, function="generate",
                       input_modality="tokens", output_modality="tokens",
                       payload={"prompt": [int(t) for t in prompt],
                                "max_new_tokens": max_new})


def _check_served(cfg, res, trace, max_new: int) -> List[int]:
    tid = res.task_id
    _check(res.status == "completed",
           f"{tid}: status {res.status} ({trace.rejected_reason})")
    _check(trace.served_by == "substrate",
           f"{tid}: served by {trace.served_by}, not the engine")
    tokens = list(res.output["tokens"])
    _check(len(tokens) == max_new,
           f"{tid}: {len(tokens)} tokens, asked for {max_new}")
    _check(all(0 <= t < cfg.vocab_size for t in tokens),
           f"{tid}: token outside the vocabulary of {cfg.vocab_size}")
    return tokens


def _serve_layout(cfg, params, warm_prompts: Sequence[np.ndarray],
                  prompts: Sequence[np.ndarray], *, paged: bool,
                  batch_size: int, max_seq: int, max_new: int,
                  log: Callable[[str], None]) -> List[List[int]]:
    """Serve ``warm_prompts`` one at a time through ``Orchestrator.submit``
    (this compiles every prompt length), then ``prompts`` concurrently: even
    tasks through ``Orchestrator.submit``, odd ones through the gateway and
    client.  Returns the tokens of every task, warm-up first."""
    layout = "paged" if paged else "slot"
    orch = Orchestrator(plane=f"smoke-{layout}")
    adapter = LmServingAdapter(cfg, params=params, batch_size=batch_size,
                               max_seq=max_seq, paged=paged)
    orch.register(adapter)
    gw = ControlPlaneGateway(orch, plane=f"smoke-{layout}",
                             workers=len(prompts)).start()
    client = ControlPlaneClient(gw.url, timeout_s=600.0)
    try:
        t0 = time.perf_counter()
        warm_tokens = [
            _check_served(cfg, *orch.submit(_task(f"{layout}-warm-{i}", p,
                                                  max_new)), max_new)
            for i, p in enumerate(warm_prompts)]
        warm_s = time.perf_counter() - t0

        def one(i: int):
            task = _task(f"{layout}-{i}", prompts[i], max_new)
            if i % 2 == 0:
                return orch.submit(task)
            return client.invoke(task)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
            outcomes = list(pool.map(one, range(len(prompts))))
        serve_s = time.perf_counter() - t0
        tokens = [_check_served(cfg, res, trace, max_new)
                  for res, trace in outcomes]
        m = adapter.engine.metrics
        _check(m["deadline_expired"] == 0,
               f"{layout}: {m['deadline_expired']} requests expired")
        if paged:
            adapter.engine.audit_pages()
        step_ms = adapter.cost.step_ms()
        log(f"smoke {layout}: {len(prompts)} tasks x {max_new} tokens "
            f"(half via Orchestrator.submit, half via the gateway); "
            f"warm-up incl. compile {warm_s:.2f} s, served in "
            f"{serve_s:.2f} s, {m['decode_steps']} decode steps, median "
            f"step {step_ms:.3f} ms")
        return warm_tokens + tokens
    finally:
        client.close()
        gw.stop()
        adapter.close()
        if adapter.engine is not None:
            adapter.engine.flush()        # frees the decode cache


def _check_numerics(cfg, params, prompt: np.ndarray, *, batch_size: int,
                    max_seq: int, log: Callable[[str], None]) -> Dict:
    """Flash-kernel prefill vs jnp prefill, and one decode step through a
    ``batch_size`` x ``max_seq`` cache vs a fresh prefill of the same
    tokens — logits compared at :data:`LOGIT_RTOL`."""
    rtol = LOGIT_RTOL[cfg.compute_dtype]
    prefill_kernel = jax.jit(build_prefill_step(
        dataclasses.replace(cfg, use_pallas=True)))
    prefill_jnp = jax.jit(build_prefill_step(
        dataclasses.replace(cfg, use_pallas=False)))
    S = len(prompt)
    tokens = jnp.asarray(prompt[None, :], jnp.int32)
    pcache, logits_kernel = prefill_kernel(params, {"tokens": tokens})
    _, logits_jnp = prefill_jnp(params, {"tokens": tokens})

    @jax.jit
    def prime(pcache):
        row = extend_cache(decode_cache(cfg, 1, max_seq), pcache, S)
        return write_slots(decode_cache(cfg, batch_size, max_seq), row,
                           jnp.zeros((1,), jnp.int32))

    nxt = int(jnp.argmax(logits_kernel[0]))
    step_tokens = np.zeros((batch_size, 1), np.int32)
    step_tokens[0, 0] = nxt
    pos = np.zeros((batch_size,), np.int32)
    pos[0] = S
    decode = jax.jit(build_decode_step(cfg), donate_argnums=1)
    _, logits_decode = decode(params, prime(pcache), jnp.asarray(step_tokens),
                              jnp.asarray(pos))
    _, logits_fresh = prefill_kernel(
        params, {"tokens": jnp.asarray(np.append(prompt, nxt)[None, :],
                                       jnp.int32)})
    logits = {"prefill_kernel": logits_kernel[0], "prefill_jnp": logits_jnp[0],
              "decode": logits_decode[0], "prefill_fresh": logits_fresh[0]}
    for name, lg in logits.items():
        _check(bool(jnp.all(jnp.isfinite(lg))), f"{name} logits not finite")
    flash_err = _rel_err(logits["prefill_kernel"], logits["prefill_jnp"])
    decode_err = _rel_err(logits["decode"], logits["prefill_fresh"])
    log(f"smoke numerics (prompt {S}, {cfg.compute_dtype}, rtol {rtol}): "
        f"flash vs jnp prefill {flash_err:.6g}; "
        f"decode through cache vs fresh prefill {decode_err:.6g}")
    _check(flash_err <= rtol,
           f"flash prefill differs from jnp prefill: {flash_err:.4g} > {rtol}")
    _check(decode_err <= rtol,
           f"cached decode differs from fresh prefill: {decode_err:.4g} > "
           f"{rtol}")
    return {"flash_vs_jnp": flash_err, "decode_vs_prefill": decode_err,
            "rtol": rtol}


def run(cfg, *, batch_size: int, max_seq: int, prompt_lens: Sequence[int],
        n_tasks: int, max_new_tokens: int, seed: int = 0,
        log: Callable[[str], None] = print) -> Dict:
    """Serve ``n_tasks`` seeded random prompts (lengths cycling through
    ``prompt_lens``) on both KV layouts, check them, then check the
    numerics.  Returns the served tokens by layout and the logit errors;
    raises :class:`SmokeCheckFailed` on any failed check."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model_specs(cfg), seed))
    log(f"smoke params: {cfg.name} ({cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.param_dtype}) drawn "
        f"in {time.perf_counter() - t0:.2f} s")
    rng = np.random.default_rng(seed)
    # the warm-up prompts are drawn apart from the served ones: a served
    # prompt that repeated one would take the paged layout's prefix-cache
    # path, whose suffix prefill rounds differently from a full prefill
    warm_prompts = [rng.integers(1, cfg.vocab_size, n, dtype=np.int32)
                    for n in sorted(set(prompt_lens))]
    prompts = [rng.integers(1, cfg.vocab_size, prompt_lens[i % len(prompt_lens)],
                            dtype=np.int32)
               for i in range(n_tasks)]
    tokens = {("paged" if paged else "slot"): _serve_layout(
        cfg, params, warm_prompts, prompts, paged=paged,
        batch_size=batch_size, max_seq=max_seq, max_new=max_new_tokens,
        log=log) for paged in (False, True)}
    for i, (a, b) in enumerate(zip(tokens["slot"], tokens["paged"])):
        _check(a == b, f"task {i} (warm-up first): slot-granular and paged "
                       f"tokens differ")
    numerics = _check_numerics(cfg, params, max(prompts, key=len),
                               batch_size=batch_size, max_seq=max_seq, log=log)
    return {"tokens": tokens, "numerics": numerics}
