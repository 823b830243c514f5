"""Serving engines: the LP service driver + the LM continuous batcher.

Two independent serving shapes live here:

  * ``ServiceDriver`` / ``ReadBatcher`` / ``ReadTicket`` — the async
    machinery behind ``serving.lp_service.LPService``.  A background
    thread clocks the service (admission-window deadlines fire with zero
    caller traffic, finished solves commit off every caller's critical
    path) and fuses the read tickets of concurrent callers into ONE
    jitted device gather against the committed ``DeviceLabelView``
    (docs/serving.md §The background driver).
  * ``ServeEngine`` — slot-based continuous batching over an LM
    ``decode_step``: a fixed pool of B slots, prefill into a free slot,
    then the whole pool decodes one token per step.  The batch axis of
    every cache leaf is probed once at init by differencing
    ``cache_shape(b)`` vs ``cache_shape(b+1)``, so it works unchanged
    for KV caches, recurrent states and enc-dec caches.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------- #
# LP serving: read tickets, the fusing batcher, and the service driver
# ---------------------------------------------------------------------- #

class ReadTicket:
    """One caller's pending read: ids in, (QueryResult | error) out.

    Handed out by ``LPService.query_async``; the driver fulfils batches
    of these with one fused device gather.  ``wait`` blocks the caller;
    ``completed_at`` stamps fulfilment time so open-loop benchmarks can
    measure latency from the *scheduled* arrival, not the wait call
    (coordinated-omission-free, see benchmarks/serve_lp.py).
    """

    __slots__ = ("ids", "cutoff", "enqueued_at", "completed_at",
                 "result", "error", "_done")

    def __init__(self, ids: np.ndarray, cutoff: float):
        self.ids = ids
        self.cutoff = cutoff
        self.enqueued_at = time.perf_counter()
        self.completed_at: float | None = None
        self.result = None
        self.error: BaseException | None = None
        self._done = threading.Event()

    def _fulfil(self, result=None, error=None):
        self.result = result
        self.error = error
        self.completed_at = time.perf_counter()
        self._done.set()

    @property
    def done(self) -> bool:
        """True once the driver has fulfilled (or failed) this ticket."""
        return self._done.is_set()

    def wait(self, timeout: float | None = None):
        """Block until fulfilled; returns the ``QueryResult`` (raises the
        driver-side error, or TimeoutError on timeout)."""
        if not self._done.wait(timeout):
            raise TimeoutError("read ticket not fulfilled in time")
        if self.error is not None:
            raise self.error
        return self.result


class ReadBatcher:
    """Thread-safe queue of pending ``ReadTicket``s.

    Callers ``submit``; the driver ``take_all``s and serves the whole
    batch from ONE committed view in one fused gather — which is also
    the coherence argument: every ticket in a batch is answered from
    the same immutable snapshot, so a commit landing mid-burst flips
    readers atomically between views, never within one.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tickets: list[ReadTicket] = []
        self._wake = threading.Event()
        self._closed = False

    def submit(self, ids: np.ndarray, cutoff: float) -> ReadTicket:
        """Queue a read for the driver's next fused gather."""
        t = ReadTicket(ids, cutoff)
        with self._lock:
            if self._closed:
                raise RuntimeError("read batcher is closed (driver stopped)")
            self._tickets.append(t)
        self._wake.set()
        return t

    def take_all(self) -> list[ReadTicket]:
        """Drain the queue (driver side): all tickets, atomically."""
        with self._lock:
            tickets, self._tickets = self._tickets, []
        return tickets

    @property
    def pending(self) -> int:
        """Tickets queued but not yet taken by the driver."""
        with self._lock:
            return len(self._tickets)

    def close(self) -> list[ReadTicket]:
        """Refuse new submissions; returns whatever was still queued so
        the driver can drain it."""
        with self._lock:
            self._closed = True
            tickets, self._tickets = self._tickets, []
        return tickets

    def wait_for_work(self, timeout: float):
        """Park the driver until a submit arrives or ``timeout`` lapses."""
        self._wake.wait(timeout)
        self._wake.clear()


class ServiceDriver(threading.Thread):
    """Background clock for an ``LPService`` (docs/serving.md).

    One loop iteration: fulfil every queued read ticket with a single
    fused gather, then ``pump`` the service under its lock — committing
    a finished solve and force-admitting the open window once its
    ``window_ms`` deadline passes, with NO caller traffic required.
    Between iterations the thread sleeps on the batcher's wake event,
    capped by the time to the next admission deadline (so deadlines
    fire promptly) and ``poll_ms`` (so finished solves commit promptly).

    ``stop`` drains: in-flight tickets are fulfilled before the thread
    exits, and the batcher is closed so late submitters get a clean
    error instead of hanging.  If a tick raises (an admit, solve or
    commit that failed), the loop ends and keeps the exception in
    ``error``: queued tickets fail with it, and ``LPService`` re-raises
    it to its next writer or ``stop`` — a dead clock never goes unseen.
    """

    def __init__(self, service, batcher: ReadBatcher, poll_ms: float = 2.0):
        super().__init__(name="lp-service-driver", daemon=True)
        self._svc = service
        self._batcher = batcher
        self._poll_s = poll_ms / 1e3
        self._halt = threading.Event()
        self.read_batches = 0  # fused gathers executed
        self.read_tickets = 0  # tickets fulfilled by those gathers
        self.deadline_admissions = 0  # windows admitted by the clock
        self.error: BaseException | None = None  # what ended the loop

    def run(self):
        """Driver loop: fuse queued reads, pump the service's admission
        clock, exit only after a halt request has drained stragglers."""
        try:
            self._loop()
        except Exception as e:  # re-raised to callers by LPService
            self.error = e
            for t in self._batcher.close():
                t._fulfil(error=e)

    def _loop(self):
        trace = self._svc.engine.trace
        while True:
            tickets = self._batcher.take_all()
            if tickets:
                now = time.perf_counter()
                for t in tickets:
                    trace.interval("lp.read.queue", now - t.enqueued_at)
                self._serve(tickets)
            admitted = self._svc._driver_pump()
            self.deadline_admissions += admitted
            if self._halt.is_set():
                if self._batcher.pending:
                    continue  # drain stragglers before exiting
                break
            self._batcher.wait_for_work(
                min(self._poll_s, self._svc._time_to_deadline()))

    def _serve(self, tickets: list[ReadTicket]):
        try:
            results = self._svc._serve_reads(tickets)
        except BaseException as e:  # noqa: BLE001 — tickets must not hang
            for t in tickets:
                t._fulfil(error=e)
            return
        self.read_batches += 1
        self.read_tickets += len(tickets)
        for t, r in zip(tickets, results):
            t._fulfil(result=r)

    def halt(self):
        """Signal the loop to exit WITHOUT joining.  Safe to call from
        the driver thread itself — the service's preemption handler runs
        inside ``pump()``, which the driver may be clocking — where
        ``stop()``'s self-join would deadlock.  The loop still drains
        queued tickets before exiting; call ``stop()`` from another
        thread afterwards to join and close the batcher."""
        self._halt.set()
        self._batcher._wake.set()

    def stop(self, timeout: float = 30.0):
        """Signal, drain in-flight tickets, join; then fulfil anything
        that raced past the close with an error so no caller hangs."""
        self.halt()
        self.join(timeout)
        for t in self._batcher.close():
            t._fulfil(error=RuntimeError("service driver stopped"))


@dataclasses.dataclass
class Request:
    """One decode request: prompt tokens in, generated tokens out."""

    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Slot-based continuous-batching decode loop (the KV-cache serving
    exemplar the ServiceDriver's fused-read design borrows from)."""

    def __init__(self, model, params, max_batch: int = 4, s_max: int = 256):
        self.model = model
        self.params = params
        self.b = max_batch
        self.s_max = s_max
        self.cache = model.init_cache(max_batch, s_max)
        sa = model.cache_shape(max_batch, s_max)
        sb = model.cache_shape(max_batch + 1, s_max)
        self.batch_axes = jax.tree.map(
            lambda a, b_: next(i for i, (x, y) in enumerate(
                zip(a.shape, b_.shape)) if x != y), sa, sb)
        self.pos = np.zeros(max_batch, np.int64)
        self.slots: list[Request | None] = [None] * max_batch
        self._decode = jax.jit(model.decode_step)
        self.steps = 0

    # ------------------------------------------------------------------ #
    def _free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _commit_slot(self, new_cache, slot: int):
        """Adopt only ``slot``'s rows from new_cache (other slots frozen)."""

        def leaf(new, old, axis):
            """Copy one slot's rows along this leaf's batch axis."""
            idx = [slice(None)] * new.ndim
            idx[axis] = slice(slot, slot + 1)
            return old.at[tuple(idx)].set(new[tuple(idx)])

        self.cache = jax.tree.map(leaf, new_cache, self.cache, self.batch_axes)

    def submit(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot; False when all slots busy."""
        slot = self._free_slot()
        if slot is None:
            return False
        self.pos[slot] = 0
        self.slots[slot] = req
        logits = None
        for tok in req.prompt:  # slot-local prefill at the slot's own pos
            pos_vec = self.pos.copy()
            pos_vec[slot] = self.pos[slot]
            batch = {
                "tokens": jnp.full((self.b, 1), int(tok), jnp.int32),
                "pos": jnp.asarray(pos_vec, jnp.int32),
            }
            logits, cache = self._decode(self.params, self.cache, batch)
            self._commit_slot(cache, slot)
            self.pos[slot] += 1
        req.out.append(int(jnp.argmax(logits[slot, -1])))
        return True

    # ------------------------------------------------------------------ #
    def step(self):
        """One batched decode step for every active slot."""
        if not any(s is not None for s in self.slots):
            return
        toks = np.zeros((self.b, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None and req.out:
                toks[i, 0] = req.out[-1]
        # per-slot positions: continuous batching, every slot at its own pos
        batch = {"tokens": jnp.asarray(toks),
                 "pos": jnp.asarray(self.pos, jnp.int32)}
        logits, self.cache = self._decode(self.params, self.cache, batch)
        self.steps += 1
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.pos[i] += 1
            req.out.append(int(nxt[i]))
            if len(req.out) >= req.max_new:
                req.done = True
                self.slots[i] = None

    def run(self, requests: list[Request], max_steps: int = 1_000):
        """Drive all ``requests`` to completion (admit-as-slots-free)."""
        pending = list(requests)
        while (pending or any(s is not None for s in self.slots)) \
                and self.steps < max_steps:
            while pending and self._free_slot() is not None:
                self.submit(pending.pop(0))
            self.step()
        return [r for r in requests if r.done]
