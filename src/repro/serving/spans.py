"""Named host spans of the serving engine, on the profiler's clock.

A :class:`span` opens a ``jax.profiler.TraceAnnotation``, so a profile of
the serving process shows each engine phase on the same clock as the
device's programs, and every idle gap of the device can be put down to
what the host was doing in it.  With no profiler running the annotation is
a cheap no-op.  Given ``metrics`` and ``key``, the span also adds its
elapsed milliseconds (``time.perf_counter``) to ``metrics[key]``, which is
how the engine's own duration counters are kept.

The engine updates its counters only while holding its lock; a span given
a ``key`` must therefore close while the caller holds it.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class span:
    """``with span("engine.decode", metrics, "decode_ms") as s: ...``;
    ``s.ms`` holds the elapsed milliseconds once the block has closed.
    :meth:`start` and :meth:`stop` open and close it where a ``with`` block
    cannot, as around the acquisition of a lock; :meth:`tag` attaches
    arguments to the annotation while it is open (``new_shape=1``)."""

    __slots__ = ("_ann", "_metrics", "_key", "_t0", "ms")

    def __init__(self, name: str, metrics: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None):
        self._ann = TraceAnnotation(name)
        self._metrics, self._key = metrics, key
        self.ms = 0.0

    def start(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        if self._key is not None:
            self._metrics[self._key] += self.ms
        self._ann.__exit__(None, None, None)

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()

    def tag(self, **args) -> None:
        self._ann.set_metadata(**args)
