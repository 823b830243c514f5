"""One general traffic generator, driven by a mix file in ``traffic/``.

A mix names the loop (``open``: Poisson arrivals on a fixed schedule;
``closed``: one writer that keeps the service's pending queue full), the
share of each write kind, how ids are chosen, and the read load.  Every
request carries ``ops_per_request`` ops of one kind.  The request
sequence depends on the seed alone, never on timing: ids of inserted
rows, the oldest live row and the live range are tracked as the
sequence is made, and a read takes the live range as of the writes
scheduled before it.

Write kinds: ``insert`` (new unlabelled rows from the seeded pool),
``delete`` (the oldest live rows), ``relabel`` (``relabel_ids`` =
``zipf_live`` over the live rows, ``zipf_preloaded`` over the live rows
of the preload, which never relabels a row in the window that inserts
it, or ``preload_unlabelled`` to walk the preload's unlabelled rows once
each in a seeded order).  A relabelled row
gets its generator class, or, with ``relabel_labelled_share`` below 1, a
target drawn once per row from the seed: its class with that share, else
unlabelled (a retracted label).  Drawn at the preload's labelled share,
a touched row is labelled as often as an untouched one, so the
unlabelled rows the solve works on keep their number through the run.
Reads take ``read_ids`` ids Zipf over the live rows.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

import lp

KINDS = ("insert", "delete", "relabel")


@dataclasses.dataclass
class Write:
    kind: str
    ops: int
    ins_rows: tuple[int, int] = (0, 0)  # [lo, hi) into the data pool
    ids: np.ndarray | None = None  # delete or relabel ids
    labels: np.ndarray | None = None  # relabel labels


class Zipf:
    """Zipf(theta) ranks over ``n`` items, scrambled onto positions."""

    def __init__(self, n: int, theta: float, rng: np.random.Generator):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w) / w.sum()
        self.rng = rng
        self.salt = int(rng.integers(0, 2**31))

    def positions(self, size: int, n_live: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, self.rng.random(size)).astype(np.uint64)
        h = (r * np.uint64(0x9E3779B1) + np.uint64(self.salt)) & np.uint64(0xFFFFFFFF)
        return (h % np.uint64(max(n_live, 1))).astype(np.int64)


class WriteStream:
    """The seeded sequence of write requests, made in order."""

    def __init__(self, mix: dict, cls: np.ndarray, labels0: np.ndarray, n0: int,
                 pool_rows: int, seed_word: int):
        self.mix = mix
        self.ops = int(mix["ops_per_request"])
        shares = mix["write_mix"]
        self.kinds = [k for k in KINDS if shares.get(k, 0) > 0]
        p = np.array([shares[k] for k in self.kinds], np.float64)
        self.p = p / p.sum()
        self.rng = np.random.default_rng(seed_word)
        self.n0 = n0
        self.pool_end = n0 + pool_rows
        self.head = 0  # oldest live id (deletes take the oldest)
        self.next_id = n0  # id the next inserted row gets
        self.zipf = (Zipf(n0, float(mix.get("zipf_theta", 0.99)), self.rng)
                     if {"zipf_live", "zipf_preloaded"} & {mix.get("relabel_ids"),
                                                           mix.get("read_id_dist")} else None)
        self.target = cls.astype(np.int8)  # the label a relabel sets, per row
        share = float(mix.get("relabel_labelled_share", 1.0))
        if share < 1.0:
            keep = self.rng.random(len(cls)) < share
            self.target = np.where(keep, self.target, lp.UNLABELLED).astype(np.int8)
        self.relabel_pool = None
        if mix.get("relabel_ids") == "preload_unlabelled":
            self.relabel_pool = self.rng.permutation(np.flatnonzero(labels0 == lp.UNLABELLED))
            self.relabel_at = 0

    def live_range(self) -> tuple[int, int]:
        return self.head, self.next_id

    def next(self, kind: str | None = None) -> Write:
        """The next request: of the mix's kinds drawn by share, or ``kind``."""
        if kind is None:
            kind = self.kinds[int(self.rng.choice(len(self.kinds), p=self.p))]
        ops = self.ops
        if kind == "insert":
            lo = self.next_id
            if lo + ops > self.pool_end:
                raise RuntimeError("insert pool exhausted; raise insert_pool_rows")
            self.next_id += ops
            return Write(kind, ops, ins_rows=(lo, lo + ops))
        if kind == "delete":
            ids = np.arange(self.head, self.head + ops, dtype=np.int64)
            self.head += ops
            return Write(kind, ops, ids=ids)
        if self.relabel_pool is not None:
            ids = self.relabel_pool[self.relabel_at:self.relabel_at + ops]
            if len(ids) < ops:
                raise RuntimeError("relabel pool exhausted")
            self.relabel_at += ops
        else:
            lo, hi = self.live_range()
            if self.mix.get("relabel_ids") == "zipf_preloaded":
                hi = min(hi, self.n0)
            ids = lo + self.zipf.positions(ops, hi - lo)
        ids = np.asarray(ids, np.int64)
        return Write(kind, ops, ids=ids, labels=self.target[ids])


def mutate_args(w: Write, emb: np.ndarray) -> dict:
    if w.kind == "insert":
        lo, hi = w.ins_rows
        return {"ins_emb": emb[lo:hi],
                "ins_labels": np.full(hi - lo, lp.UNLABELLED, np.int8)}
    if w.kind == "delete":
        return {"del_ids": w.ids}
    return {"rel_ids": w.ids, "rel_labels": w.labels}


def poisson_times(rate: float, horizon: float, rng: np.random.Generator) -> np.ndarray:
    if rate <= 0:
        return np.zeros(0)
    n = int(rate * horizon * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return t[t < horizon]


@dataclasses.dataclass
class Plan:
    """Everything the drivers send, made before the first request."""

    writes: list  # open loop: every Write up to the horizon
    write_t: np.ndarray  # open loop: schedule offsets (s)
    read_t: np.ndarray  # schedule offsets (s)
    read_ids: list  # per read: ids
    stream: WriteStream  # closed loop draws from it as it goes


def make_plan(mix: dict, stream: WriteStream, horizon: float, seed_word: int) -> Plan:
    rng = np.random.default_rng(seed_word)
    reads_per_s = float(mix.get("read_requests_per_s", 0))
    if mix["loop"] == "closed":
        if reads_per_s:
            raise ValueError("a closed-loop mix carries no reads")
        return Plan([], np.zeros(0), np.zeros(0), [], stream)
    write_t = poisson_times(float(mix["write_ops_per_s"]) / stream.ops, horizon, rng)
    writes, ranges = [], []
    for _ in write_t:
        ranges.append(stream.live_range())
        writes.append(stream.next())
    read_t = poisson_times(reads_per_s, horizon, rng)
    k = np.searchsorted(write_t, read_t)  # writes scheduled before each read
    zr = Zipf(stream.n0, float(mix.get("zipf_theta", 0.99)), rng)
    read_ids = []
    for j in k:
        lo, hi = ranges[j] if j < len(ranges) else stream.live_range()
        read_ids.append(lo + zr.positions(int(mix["read_ids"]), hi - lo))
    return Plan(writes, write_t, read_t, read_ids, stream)


@dataclasses.dataclass
class Sent:
    sched: float  # perf_counter the request was due (closed loop: when sent)
    sent: float
    write: Write | None = None
    ticket: object = None  # MutationTicket / ReadTicket
    ids: np.ndarray | None = None


class Driver:
    """Writer and reader threads against a running ``LPService``."""

    def __init__(self, svc, plan: Plan, emb: np.ndarray, mix: dict):
        self.svc, self.plan, self.emb, self.mix = svc, plan, emb, mix
        self.writes: list[Sent] = []
        self.reads: list[Sent] = []
        self.stop = threading.Event()
        self.until = float("inf")  # no request scheduled after this is sent
        self.errors: list[BaseException] = []
        self.threads: list[threading.Thread] = []

    def start(self, t0: float) -> None:
        self.t0 = t0
        target = self._open_writer if self.mix["loop"] == "open" else self._closed_writer
        self.threads = [threading.Thread(target=self._guard, args=(target,), daemon=True)]
        if len(self.plan.read_t):
            self.threads.append(threading.Thread(target=self._guard, args=(self._reader,),
                                                 daemon=True))
        for th in self.threads:
            th.start()

    def _guard(self, fn):
        try:
            fn()
        except BaseException as e:  # surfaced by the harness after the run
            self.errors.append(e)
            self.stop.set()

    def _wait_until(self, t: float) -> bool:
        while True:
            now = time.perf_counter()
            if self.stop.is_set():
                return False
            if now >= t:
                return True
            time.sleep(min(t - now, 0.005))

    def _open_writer(self):
        for w, dt in zip(self.plan.writes, self.plan.write_t):
            sched = self.t0 + dt
            if sched > self.until or not self._wait_until(sched) or sched > self.until:
                return
            s = Sent(sched, time.perf_counter(), write=w)
            s.ticket = self.svc.mutate(**mutate_args(w, self.emb))
            self.writes.append(s)

    def _closed_writer(self):
        while not self.stop.is_set() and time.perf_counter() <= self.until:
            w = self.plan.stream.next()
            now = time.perf_counter()
            s = Sent(now, now, write=w)
            s.ticket = self.svc.mutate(**mutate_args(w, self.emb))
            self.writes.append(s)

    def _reader(self):
        for ids, dt in zip(self.plan.read_ids, self.plan.read_t):
            sched = self.t0 + dt
            if sched > self.until or not self._wait_until(sched) or sched > self.until:
                return
            s = Sent(sched, time.perf_counter(), ids=ids)
            s.ticket = self.svc.query_async(ids)
            if s.ticket is None:
                raise RuntimeError("read refused: the service driver is not running")
            self.reads.append(s)

    def finish(self, until: float) -> None:
        """Send what is scheduled up to ``until``, however late, then stop."""
        self.until = until
        for th in self.threads:
            th.join()

    def halt(self) -> None:
        self.stop.set()
        for th in self.threads:
            th.join()
