"""Spans, intervals and counters of the served write path, as running totals.

A span times a code region.  It runs inside
``jax.profiler.TraceAnnotation``, so a profile shows it on the host
timeline, on the same clock as the device's ``XLA Ops``/``XLA Modules``
lines, and its duration adds to ``(count, total)`` under its name.  An
interval records a wait that is not a code region (the time between two
stamps) into the same totals.  A counter is a plain sum.

There is no switch: the profiler carries the timeline when it runs, and
the totals carry the means at all times (``snapshot``; the difference of
two snapshots covers what happened between them).  ``StreamEngine`` owns
one recorder (``engine.trace``); ``LPService`` records into its engine's.
"""

from __future__ import annotations

import threading
import time

import jax


class _Span:
    __slots__ = ("_rec", "_name", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str, meta: dict):
        self._rec = rec
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._rec._add_ns(self._name, time.perf_counter_ns() - self._t0)
        self._ann.__exit__(*exc)


class Recorder:
    """Thread-safe totals of named spans and intervals, and counters."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: dict[str, list[int]] = {}  # name -> [count, total ns]
        self._counters: dict[str, int] = {}

    def span(self, name: str, **meta) -> _Span:
        """Context manager timing its body under ``name``; ``meta`` goes to
        the profile event only (e.g. ``batch=`` to tie a window's spans)."""
        return _Span(self, name, meta)

    def interval(self, name: str, seconds: float) -> None:
        """Record a wait of ``seconds`` that no code region spans."""
        self._add_ns(name, int(seconds * 1e9))

    def add(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def snapshot(self) -> tuple[dict[str, tuple[int, float]], dict[str, int]]:
        """``({name: (count, total_ms)}, {counter: value})``, copies."""
        with self._lock:
            spans = {n: (c, ns / 1e6) for n, (c, ns) in self._totals.items()}
            return spans, dict(self._counters)

    def _add_ns(self, name: str, ns: int) -> None:
        with self._lock:
            tot = self._totals.get(name)
            if tot is None:
                self._totals[name] = [1, ns]
            else:
                tot[0] += 1
                tot[1] += ns
