"""LM serving engine: continuous batching over the KV cache substrate.

One decode loop serves every request: :meth:`ServingEngine.submit` puts a
request on the waiting queue, and :meth:`ServingEngine.step` advances the
shared decode batch one token.  Each batch slot owns an independent
timeline: a freed slot is re-primed from a fresh B=1 prefill (the jitted
``_prime``) and the per-row position vector keeps every other sequence
exact in the jitted ``_decode``.  Requests join and leave the batch every
step.  :meth:`ServingEngine.drain` steps until the engine is empty;
:meth:`ServingEngine.serve_forever` is the driver thread's loop.

KV storage comes in two layouts, decided by :func:`kv_pool_pages`:

- **slot-granular** (default) — every batch slot owns a contiguous
  ``max_seq`` row of the decode cache, whether the request uses 9 tokens or
  all of them.  Capacity = ``batch_size`` requests of ``max_seq`` tokens.
- **paged** (``paged=True``) — global-attention K/V and MLA latents live in
  a shared pool of fixed-size token pages (``serving/kv_pages.py``)
  addressed through per-row page tables; pages are allocated on demand as
  sequences grow and refcounted so requests sharing a prompt prefix share
  its pages (prefix cache: suffix-only prefill).  Capacity is priced in
  *pages actually used*: admission reserves a request's worst-case page
  need and refuses with a structured ``QUEUE_SATURATED`` (+
  ``retry_after_s``) when the pool cannot hold it — the reservation is
  what guarantees mid-decode page allocation never fails.  Bounded
  per-row state (ring-buffer windows, recurrent/rwkv carries, cross K/V)
  stays slot-granular; archs with no pageable leaves degrade gracefully to
  the slot-granular path.

Per-request serving telemetry (TTFT, decode tokens/s) is stamped on the
:class:`Request` via the engine's injected :class:`~repro.core.simclock.Clock`
(``clock=`` ctor arg — the PR 8 simulator can drive serving on virtual
time); the control-plane adapter (``repro.substrates.lm_serving``) forwards
it to the ``TelemetryBus``.

Each phase of the engine is a named host span (``serving/spans.py``), so a
profile of the serving process puts every device-idle gap down to what the
host was doing.  The driver thread opens ``engine.step`` (the whole of
:meth:`ServingEngine.step`) and inside it ``engine.admit`` (the admissions),
``engine.prime`` (one admission: page lookup, dispatch, first token ready),
``engine.prepare`` (tokens, positions, page growth, the page-table upload),
``engine.decode`` (dispatch to logits ready), ``engine.sample`` (argmax and
copy to the host) and ``engine.emit`` (tokens appended, rows finished,
``on_complete``); ``engine.park`` is the idle wait of ``serve_forever``.
Caller threads open ``engine.submit`` and, inside it, ``engine.submit.lock``
(the wait for the engine's lock).  The first dispatch of each program shape
carries ``new_shape=1``.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import AdmissionRefused, ErrorCode
from repro.core.simclock import SYSTEM_CLOCK, Clock
from repro.models import (build_decode_step, build_decode_step_paged,
                          build_prefill_past_step, build_prefill_step,
                          decode_cache, decode_cache_paged, model_specs,
                          paged_cache_flags, paged_support)
from repro.models.common import init_params
from repro.serving.cache_utils import (extend_cache, gather_pages,
                                       write_prefill_paged, write_slots)
from repro.serving.kv_pages import PagePool, PrefixCache
from repro.serving.spans import span


@dataclasses.dataclass
class Request:
    request_id: str
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 8
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: optional absolute deadline (engine-clock monotonic seconds); admission
    #: may refuse a request predicted to finish past it
    deadline_s: Optional[float] = None
    #: serving telemetry (engine-clock monotonic stamps, engine-filled)
    arrived_s: Optional[float] = None
    #: when the request joined the waiting queue (after admission)
    enqueued_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finished_s: Optional[float] = None
    #: True when the request finished after its deadline (admitted requests
    #: should never see this if admission predicts correctly)
    expired: bool = False
    #: pages reserved against the kv pool at admission (paged mode only;
    #: engine bookkeeping, not wire state)
    reserved_pages: int = 0

    @property
    def ttft_ms(self) -> Optional[float]:
        """Time to first token (arrival → first emitted token)."""
        if self.arrived_s is None or self.first_token_s is None:
            return None
        return (self.first_token_s - self.arrived_s) * 1e3

    @property
    def tokens_per_s(self) -> Optional[float]:
        """Decode throughput over the request's full residency."""
        if (self.arrived_s is None or self.finished_s is None
                or not self.generated):
            return None
        dur = self.finished_s - self.arrived_s
        return len(self.generated) / dur if dur > 0 else None


@dataclasses.dataclass
class _Slot:
    """One row of the shared decode batch."""

    index: int
    request: Optional[Request] = None
    pos: int = 0                        # next cache position this row writes
    token: int = 0                      # last emitted token (next decode input)
    #: page ids owned by this row, in block order (paged mode; includes
    #: shared prefix pages — every page holds one of the request's refs)
    pages: List[int] = dataclasses.field(default_factory=list)


def kv_pool_pages(cfg, paged: bool, batch_size: int, max_seq: int,
                  page_size: int, pool_pages: Optional[int]) -> int:
    """Pages in the KV pool of an engine built with these arguments:
    ``pool_pages`` as given, else enough for every row to hold ``max_seq``
    tokens.  0 when paging is off or no cache leaf of ``cfg`` can page
    (pure recurrent or ring stacks): every leaf is then slot-granular."""
    if not (paged and paged_support(cfg)[0]):
        return 0
    if pool_pages is not None:
        return pool_pages
    return batch_size * -(-max_seq // page_size)


class ServingEngine:
    """Continuous-batching serving engine for one ``ArchConfig``.

    ``submit`` may be called from many threads while one driver thread runs
    ``step`` (``serve_forever``); every entry point is thread-safe.

    In paged mode ``max_seq`` is the per-request token cap (the page-table
    width); aggregate capacity is the page pool, not
    ``batch_size × max_seq`` — so a paged engine admits more concurrent
    short requests than it has contiguous rows for, and a single request
    may exceed what one slot-granular row could ever hold.

    ``metrics`` holds running counters, updated under the engine's lock:
    ``prefill_ms`` and ``decode_ms`` (the ``engine.prime`` and
    ``engine.decode`` spans), ``decode_steps``, ``tokens``, ``requests``,
    ``deadline_expired``; ``submits`` (submit calls that took the lock) and
    ``lock_wait_ms`` (their ``engine.submit.lock``); ``primes`` and
    ``queue_ms`` (each primed request's wait from ``enqueued_s`` to its
    prime, on the engine clock); ``host_ms`` (each ``engine.step`` less its
    primes and decode); ``decode_rows`` (live rows summed over decode
    steps); ``state_rows`` (rows of resident state the decode steps carried,
    live or not: ``batch_size`` a step on the slot-granular path, 0 where
    every leaf pages); ``new_shapes`` (dispatches of a prime length, a (suffix,
    prefix) pair or a decode width not run before: where a trace or a
    compile lands).
    """

    def __init__(self, cfg, params=None, *, batch_size: int = 2,
                 max_seq: int = 128, seed: int = 0, paged: bool = False,
                 page_size: int = 16, pool_pages: Optional[int] = None,
                 prefix_sharing: bool = True, clock: Optional[Clock] = None):
        self.cfg = cfg
        self.batch_size = batch_size
        self.max_seq = max_seq
        self.clock = clock if clock is not None else SYSTEM_CLOCK
        self.params = params if params is not None else init_params(
            model_specs(cfg), seed)
        self._prefill = jax.jit(build_prefill_step(cfg))
        self.page_size = int(page_size)
        self.pool_pages = kv_pool_pages(cfg, paged, batch_size, max_seq,
                                        self.page_size, pool_pages)
        self._pool: Optional[PagePool] = None
        self._prefix: Optional[PrefixCache] = None
        self._tables: Optional[np.ndarray] = None
        if self.pool_pages:
            self.max_pages = -(-max_seq // self.page_size)
            self._flags = paged_cache_flags(cfg)
            self._pool = PagePool(self.pool_pages, self.page_size)
            self._tables = np.zeros((batch_size, self.max_pages), np.int32)
            self._tables_dev: Dict[int, object] = {}
            self._decode = jax.jit(
                build_decode_step_paged(cfg, self.page_size),
                donate_argnums=1)
            self._prime = jax.jit(self._prime_paged_fn, donate_argnums=2)
            if prefix_sharing and paged_support(cfg)[1]:
                self._prefix = PrefixCache(self._pool)
                self._prefill_past = build_prefill_past_step(cfg)
                self._prime_past = jax.jit(self._prime_past_fn,
                                           donate_argnums=2)
        else:
            self._decode = jax.jit(build_decode_step(cfg), donate_argnums=1)
            self._prime = jax.jit(self._prime_fn, donate_argnums=2)
        #: rows of resident state each decode step carries: every row on the
        #: slot-granular path, live or not; on the paged path every row if
        #: some leaf does not page (recurrent state, ring buffers), else none
        self._state_rows = (batch_size if self._pool is None
                            or not all(jax.tree.leaves(self._flags)) else 0)
        self.metrics: Dict[str, float] = {
            "prefill_ms": 0.0, "decode_ms": 0.0, "decode_steps": 0,
            "tokens": 0, "requests": 0, "deadline_expired": 0,
            "submits": 0, "lock_wait_ms": 0.0, "primes": 0, "queue_ms": 0.0,
            "host_ms": 0.0, "decode_rows": 0, "state_rows": 0,
            "new_shapes": 0}
        #: program shapes the continuous path has dispatched
        self._shapes: set = set()
        # continuous-batching state
        self._slots = [_Slot(i) for i in range(batch_size)]
        self._waiting: Deque[Request] = collections.deque()
        self._cb_cache = None           # shared decode cache, built lazily
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        #: called with each finished Request (adapter → telemetry/waiters)
        self.on_complete: Optional[Callable[[Request], None]] = None
        #: admission hook: called with (request, engine) before enqueue;
        #: raises AdmissionRefused to refuse (e.g. roofline deadline check)
        self.admission: Optional[Callable[[Request, "ServingEngine"], None]] = None
        #: observers feeding a cost model (ms per decode step / per prefill)
        self.on_step_ms: Optional[Callable[[float], None]] = None
        self.on_prefill_ms: Optional[Callable[[int, float], None]] = None

    def _batch_extras(self, B):
        extras = {}
        if self.cfg.family == "encdec":
            extras["frames"] = jnp.zeros(
                (B, self.cfg.encoder_frames, self.cfg.d_model),
                jnp.dtype(self.cfg.param_dtype))
        if self.cfg.family == "vision":
            extras["image_embeds"] = jnp.zeros(
                (B, self.cfg.num_image_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.param_dtype))
        return extras

    # -- validation -----------------------------------------------------------
    def _validate(self, r: Request) -> None:
        """Structured refusal instead of silent cache truncation."""
        n = len(r.prompt)
        if n == 0:
            raise AdmissionRefused(ErrorCode.BAD_REQUEST,
                                   f"{r.request_id}: empty prompt")
        if n > self.max_seq:
            raise AdmissionRefused(
                ErrorCode.BAD_REQUEST,
                f"{r.request_id}: prompt length {n} exceeds max_seq "
                f"{self.max_seq}")
        if r.max_new_tokens < 1:
            raise AdmissionRefused(
                ErrorCode.BAD_REQUEST,
                f"{r.request_id}: bad request: max_new_tokens "
                f"{r.max_new_tokens} < 1")
        if n + r.max_new_tokens > self.max_seq:
            raise AdmissionRefused(
                ErrorCode.BAD_REQUEST,
                f"{r.request_id}: kv cache overflow: prompt {n} + "
                f"max_new_tokens {r.max_new_tokens} exceeds max_seq "
                f"{self.max_seq}")

    def _emit(self, r: Request, tok: int) -> None:
        """Append one generated token; done flips at exactly max_new_tokens
        so the continuous loop can free the KV slot immediately."""
        r.generated.append(int(tok))
        if r.first_token_s is None:
            r.first_token_s = self.clock.monotonic()
        if len(r.generated) >= r.max_new_tokens:
            r.done = True
            r.finished_s = self.clock.monotonic()
            if r.deadline_s is not None and r.finished_s > r.deadline_s:
                r.expired = True
                self.metrics["deadline_expired"] += 1

    # -- continuous batching --------------------------------------------------
    def submit(self, r: Request) -> Request:
        """Validate, run admission, reserve kv pages, and enqueue.

        Raises :class:`AdmissionRefused`: ``BAD_REQUEST`` for malformed
        work, ``QUEUE_SATURATED`` (with ``retry_after_s``) when the page
        pool cannot hold the request's worst-case need, or whatever the
        admission hook raises (e.g. a roofline-predicted ``DEADLINE``) —
        all without touching engine state."""
        with span("engine.submit"):
            self._validate(r)
            if r.arrived_s is None:
                r.arrived_s = self.clock.monotonic()
            if self.admission is not None:
                self.admission(r, self)
            waited = span("engine.submit.lock", self.metrics,
                          "lock_wait_ms").start()
            with self._work:
                waited.stop()
                self.metrics["submits"] += 1
                if self._pool is not None:
                    need = self._pages_needed(
                        len(r.prompt) + r.max_new_tokens)
                    if not self._pool.reserve(need):
                        raise AdmissionRefused(
                            ErrorCode.QUEUE_SATURATED,
                            f"{r.request_id}: queue saturated: kv page pool "
                            f"cannot hold {need} more pages "
                            f"({self._pool.reserved_pages}/"
                            f"{self._pool.num_pages} reserved)",
                            detail={"retry_after_s": self._retry_after_s(),
                                    "needed_pages": need,
                                    "pool_pages": self._pool.num_pages,
                                    "pool_pages_used":
                                        self._pool.used_pages(),
                                    "reserved_pages":
                                        self._pool.reserved_pages})
                    r.reserved_pages = need
                r.enqueued_s = self.clock.monotonic()
                self._waiting.append(r)
                self._work.notify_all()
            return r

    def _new_shape(self, shape) -> bool:
        """Whether the continuous path dispatches ``shape`` for the first
        time (counted in ``new_shapes``)."""
        if shape in self._shapes:
            return False
        self._shapes.add(shape)
        self.metrics["new_shapes"] += 1
        return True

    def _pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def _retry_after_s(self) -> float:
        """Back-off hint for a saturated pool: roughly one batch drain of
        the decode tokens currently owed, at the observed step rate."""
        steps = self.metrics["decode_steps"]
        step_s = (self.metrics["decode_ms"] / steps / 1e3) if steps else 0.05
        b = self.backlog()
        drain_steps = max(1.0, b["decode_tokens"] / max(1, self.batch_size))
        return round(max(0.05, drain_steps * step_s), 3)

    def backlog(self) -> Dict[str, int]:
        """Work owed to queued + in-flight requests, split by phase:
        ``decode_tokens`` (tokens still to generate) and ``prefill_tokens``
        (un-prefilled prompt tokens of waiting requests) — the admission
        model prices the two at different rates."""
        with self._lock:
            decode = sum(r.max_new_tokens for r in self._waiting)
            decode += sum(s.request.max_new_tokens - len(s.request.generated)
                          for s in self._slots if s.request is not None)
            prefill = sum(len(r.prompt) for r in self._waiting)
            return {"decode_tokens": decode, "prefill_tokens": prefill}

    def backlog_tokens(self) -> int:
        """Total tokens of owed work (decode + un-prefilled prompt)."""
        b = self.backlog()
        return b["decode_tokens"] + b["prefill_tokens"]

    def live_slots(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s.request is not None)

    def cached_prefix_tokens(self, prompt) -> int:
        """Prompt tokens a submit would serve from the prefix cache (pure
        probe: no refs taken, no LRU touch — safe for admission pricing)."""
        if self._prefix is None:
            return 0
        with self._lock:
            return self._prefix.probe(np.asarray(prompt, np.int32),
                                      self.page_size)

    def pool_stats(self) -> Dict[str, float]:
        """Paged-capacity telemetry for the descriptor/snapshot (empty dict
        on slot-granular engines)."""
        if self._pool is None:
            return {}
        with self._lock:
            stats: Dict[str, float] = {
                "page_size": self.page_size,
                "pool_pages": self._pool.num_pages,
                "pool_pages_used": self._pool.used_pages(),
                "pool_pages_free": self._pool.free_pages(),
                "pool_utilization": round(self._pool.utilization(), 4),
            }
            if self._prefix is not None:
                stats["prefix_hit_rate"] = round(self._prefix.hit_rate(), 4)
                stats["prefix_cached_tokens"] = self._prefix.hit_tokens
            return stats

    def audit_pages(self) -> Dict[str, int]:
        """Leak audit of the page pool (consistency asserted inside)."""
        if self._pool is None:
            return {}
        with self._lock:
            return self._pool.audit()

    # -- slot-granular prime --------------------------------------------------
    def _prime_fn(self, params, batch, cb_cache, slot):
        """Fused admission kernel (jitted once per prompt length): B=1
        prefill → fit into a max_seq row → scatter into the shared decode
        cache at ``slot`` → argmax first token.  One dispatch per admission
        instead of a python-level tree walk per cache leaf (which costs
        more than several decode steps and would cap continuous-batching
        goodput on short-request traffic)."""
        S = batch["tokens"].shape[1]
        pcache, logits = self._prefill(params, batch)
        row = extend_cache(decode_cache(self.cfg, 1, self.max_seq),
                           pcache, S)
        cb = write_slots(cb_cache, row, slot)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cb, tok

    # -- paged prime ----------------------------------------------------------
    def _prime_paged_fn(self, params, batch, cb_cache, pages, slot):
        """Fused paged admission kernel (jitted per prompt length): B=1
        prefill → scatter token blocks into pool pages (resident leaves
        into the batch row) → argmax first token."""
        S = batch["tokens"].shape[1]
        pcache, logits = self._prefill(params, batch)
        cb = write_prefill_paged(self._flags, cb_cache, pcache, pages, slot,
                                 S, self.page_size)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cb, tok

    def _prime_past_fn(self, params, batch, cb_cache, pages, shared, slot):
        """Prefix-hit admission kernel (jitted per (suffix, prefix) length
        pair): gather the shared prefix pages into contiguous past K/V →
        suffix-only prefill against it → scatter the suffix blocks into the
        request's private pages."""
        S = batch["tokens"].shape[1]
        past = gather_pages(self._flags, cb_cache, shared)
        pcache, logits = self._prefill_past(params, batch, past)
        cb = write_prefill_paged(self._flags, cb_cache, pcache, pages, slot,
                                 S, self.page_size)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cb, tok

    def _alloc_pages(self, n: int) -> List[int]:
        """Allocate for already-reserved work, evicting cache-only prefix
        pages as needed.  Conservative reservations guarantee success: live
        usage never exceeds the reserved total, and everything else in the
        pool is an evictable cache reference."""
        if n == 0:
            return []
        while (self._pool.free_pages() < n and self._prefix is not None
               and self._prefix.evict_one()):
            pass
        return self._pool.alloc(n)

    def _prime_slot(self, slot: _Slot, r: Request) -> None:
        """B=1 prefill at the prompt's natural length, scattered into the
        slot's row (slot-granular) or the request's pages (paged)."""
        S = len(r.prompt)
        prompt = np.asarray(r.prompt, np.int32)
        if self._cb_cache is None:
            self._cb_cache = (
                decode_cache_paged(self.cfg, self.batch_size, self.max_seq,
                                   self.pool_pages, self.page_size)
                if self._pool is not None
                else decode_cache(self.cfg, self.batch_size, self.max_seq))
        slot_arr = jnp.asarray([slot.index], jnp.int32)
        self.metrics["primes"] += 1
        self.metrics["queue_ms"] += (
            self.clock.monotonic() - r.enqueued_s) * 1e3
        with span("engine.prime", self.metrics, "prefill_ms") as prime:
            if self._pool is not None:
                shared: List[int] = []
                if self._prefix is not None:
                    _, shared = self._prefix.lookup(prompt, self.page_size)
                prefix_tokens = len(shared) * self.page_size
                fresh = self._alloc_pages(self._pages_needed(S) - len(shared))
                slot.pages = list(shared) + fresh
                self._tables[slot.index, :] = 0
                self._tables[slot.index, :len(slot.pages)] = slot.pages
                self._tables_dev.clear()
                suffix = prompt[prefix_tokens:]
                batch = {"tokens": jnp.asarray(suffix[None, :]),
                         **self._batch_extras(1)}
                if shared:
                    shape = ("prime_past", len(suffix), prefix_tokens)
                    self._cb_cache, tok = self._prime_past(
                        self.params, batch, self._cb_cache,
                        jnp.asarray(fresh, jnp.int32),
                        jnp.asarray(shared, jnp.int32), slot_arr)
                else:
                    shape = ("prime", S)
                    self._cb_cache, tok = self._prime(
                        self.params, batch, self._cb_cache,
                        jnp.asarray(fresh, jnp.int32), slot_arr)
                if self._prefix is not None:
                    # register this prompt's full blocks for future sharers
                    self._prefix.insert(prompt, slot.pages, self.page_size)
                pf_tokens = len(suffix)
            else:
                shape = ("prime", S)
                batch = {"tokens": jnp.asarray(prompt[None, :]),
                         **self._batch_extras(1)}
                self._cb_cache, tok = self._prime(
                    self.params, batch, self._cb_cache, slot_arr)
                pf_tokens = S
            if self._new_shape(shape):
                prime.tag(new_shape=1)
            tok = int(np.asarray(jax.block_until_ready(tok))[0])
        if self.on_prefill_ms is not None:
            self.on_prefill_ms(pf_tokens, prime.ms)
        with span("engine.emit"):
            slot.request, slot.pos, slot.token = r, S, tok
            self._emit(r, tok)
            self.metrics["tokens"] += 1
            if r.done:                       # max_new_tokens == 1
                self._finish(slot)

    def _finish(self, slot: _Slot) -> None:
        r = slot.request
        if self._pool is not None:
            for pid in slot.pages:
                self._pool.decref(pid)
            slot.pages = []
            self._pool.unreserve(r.reserved_pages)
            r.reserved_pages = 0
            self._tables[slot.index, :] = 0
            self._tables_dev.clear()
        slot.request, slot.pos, slot.token = None, 0, 0
        self.metrics["requests"] += 1
        if self.on_complete is not None:
            self.on_complete(r)

    def _admit_locked(self) -> None:
        with span("engine.admit"):
            for slot in self._slots:
                if slot.request is None and self._waiting:
                    self._prime_slot(slot, self._waiting.popleft())

    def step(self) -> int:
        """Advance the shared decode batch one token.  Freed slots are
        re-primed from the waiting queue first, so sequences join and leave
        the batch every step.  Returns the number of live tokens emitted
        (0 = engine idle)."""
        # one frame from lock to release: moving the body into a helper
        # called under the lock made waiting ``submit`` calls starve for
        # many more steps (the driver re-takes the lock before they wake)
        t0 = time.perf_counter()
        with span("engine.step"), self._lock:
            busy0 = self.metrics["prefill_ms"] + self.metrics["decode_ms"]
            self._admit_locked()
            live = [s for s in self._slots if s.request is not None]
            if not live:
                self._count_host(t0, busy0)
                return 0
            with span("engine.prepare"):
                tokens = np.zeros((self.batch_size, 1), np.int32)
                posv = np.zeros((self.batch_size,), np.int32)
                for s in self._slots:
                    tokens[s.index, 0] = s.token
                    posv[s.index] = s.pos
                width = 0
                if self._pool is not None:
                    for s in live:
                        blk = s.pos // self.page_size
                        if blk >= len(s.pages):
                            # on-demand growth: this step's write position
                            # crossed into a new block; the admission-time
                            # reservation guarantees the allocation succeeds
                            s.pages.extend(self._alloc_pages(1))
                            self._tables[s.index, blk] = s.pages[-1]
                            self._tables_dev.clear()
                        width = max(width, len(s.pages))
                    # attend only over live pages: the table passed to the
                    # kernel is cropped to the widest live row, so short
                    # requests read 1-2 pages instead of a full max_seq-shaped
                    # row — the paged layout's bandwidth win.  Exact widths
                    # compile at most max_pages decode variants; wide tables
                    # bucket to powers of two to bound compile count.
                    if self.max_pages > 16:
                        width = 1 << (width - 1).bit_length()
                    width = min(width, self.max_pages)
                    # tables change only on admission/growth/finish; steps in
                    # between reuse the uploaded device copy per width
                    tables = self._tables_dev.get(width)
                    if tables is None:
                        tables = jnp.asarray(self._tables[:, :width])
                        self._tables_dev[width] = tables
            new_shape = self._new_shape(("decode", width))
            with span("engine.decode", self.metrics, "decode_ms") as decode:
                if new_shape:
                    decode.tag(new_shape=1)
                if self._pool is not None:
                    self._cb_cache, logits = self._decode(
                        self.params, self._cb_cache, jnp.asarray(tokens),
                        jnp.asarray(posv), tables)
                else:
                    self._cb_cache, logits = self._decode(
                        self.params, self._cb_cache, jnp.asarray(tokens),
                        jnp.asarray(posv))
                logits = jax.block_until_ready(logits)
            self.metrics["decode_steps"] += 1
            self.metrics["decode_rows"] += len(live)
            self.metrics["state_rows"] += self._state_rows
            if self.on_step_ms is not None:
                self.on_step_ms(decode.ms)
            with span("engine.sample"):
                tok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            with span("engine.emit"):
                for s in live:
                    self._emit(s.request, int(tok[s.index]))
                    s.token = int(tok[s.index])
                    s.pos += 1
                    if s.request.done:
                        self._finish(s)
                self.metrics["tokens"] += len(live)
            self._count_host(t0, busy0)
            return len(live)

    def _count_host(self, t0: float, busy0: float) -> None:
        """Add this step's host time (all but its primes and decode) to
        ``host_ms``."""
        busy = self.metrics["prefill_ms"] + self.metrics["decode_ms"] - busy0
        self.metrics["host_ms"] += (time.perf_counter() - t0) * 1e3 - busy

    def drain(self) -> None:
        """Run ``step`` until the queue and every slot are empty."""
        while True:
            with self._lock:
                busy = bool(self._waiting) or any(
                    s.request is not None for s in self._slots)
            if not busy:
                return
            self.step()

    def flush(self) -> None:
        """Drop all queued and in-flight work: release every reservation
        and page, clear the prefix cache, reset the decode cache.  For the
        lifecycle manager's ``flush_queue`` reset — callers guarantee no
        invoker is waiting on the flushed requests."""
        with self._work:
            if self._pool is not None:
                for r in self._waiting:
                    self._pool.unreserve(r.reserved_pages)
                    r.reserved_pages = 0
            self._waiting.clear()
            for s in self._slots:
                if s.request is not None and self._pool is not None:
                    for pid in s.pages:
                        self._pool.decref(pid)
                    self._pool.unreserve(s.request.reserved_pages)
                    s.request.reserved_pages = 0
                s.request, s.pos, s.token = None, 0, 0
                s.pages = []
            if self._prefix is not None:
                self._prefix.flush()
            if self._tables is not None:
                self._tables[:] = 0
                self._tables_dev.clear()
            self._cb_cache = None
            self._work.notify_all()

    def wake(self) -> None:
        """Nudge a parked ``serve_forever`` driver (call after setting its
        stop event — the idle park is unbounded, not a poll)."""
        with self._work:
            self._work.notify_all()

    def serve_forever(self, stop: threading.Event,
                      idle_wait_s: Optional[float] = None) -> None:
        """Driver loop for a serving thread: step while there is work, park
        on the condition variable while idle (``submit`` wakes it; pair
        ``stop.set()`` with :meth:`wake` so the parked driver observes the
        stop immediately instead of after a poll interval)."""
        def has_work() -> bool:
            return (stop.is_set() or bool(self._waiting)
                    or any(s.request is not None for s in self._slots))

        while not stop.is_set():
            if self.step() == 0:
                with span("engine.park"), self._work:
                    self.clock.wait_for(self._work, has_work,
                                        timeout=idle_wait_s)
