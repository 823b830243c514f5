"""Spans, intervals and counters of the write path (``core.trace``): the
recorder itself, the totals ``StreamEngine`` and ``LPService`` keep, and
the profile events a traced session carries under their bare names."""

import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.stream import StreamEngine
from repro.core.trace import Recorder
from repro.data.synth import StreamSpec, gaussian_mixture_stream
from repro.graph.dynamic import UNLABELED, DynamicGraph
from repro.serving.lp_service import LPService

BENCH = Path(__file__).resolve().parents[1] / "bench"

SPEC = StreamSpec(total_vertices=300, batch_size=60, seed=7,
                  class_sep=6.0, noise=0.9)
SUBMIT_PHASES = ("engine.submit.apply", "engine.submit.build",
                 "engine.submit.stage", "engine.submit.supernode",
                 "engine.submit.dispatch")
# recorded with Recorder.interval: waits between stamps, not code regions
INTERVALS = {"lp.window.wait", "engine.inflight", "lp.ack.lag",
             "lp.read.queue"}


def test_recorder_spans_intervals_and_counters():
    rec = Recorder()
    with rec.span("outer", batch=3):
        with rec.span("inner"):
            time.sleep(0.002)
        with rec.span("inner"):
            pass
    rec.interval("wait", 0.25)
    rec.interval("wait", 0.5)
    rec.add("bytes", 10)
    rec.add("bytes", 5)
    spans, counters = rec.snapshot()
    assert spans["outer"][0] == 1 and spans["inner"][0] == 2
    assert spans["outer"][1] >= spans["inner"][1] >= 2.0
    assert spans["wait"] == (2, 750.0)
    assert counters == {"bytes": 15}
    # snapshots are copies: later records leave an earlier one unchanged
    rec.add("bytes", 1)
    assert counters == {"bytes": 15}


def test_recorder_span_counts_when_its_body_raises():
    rec = Recorder()
    try:
        with rec.span("failing"):
            raise KeyError("x")
    except KeyError:
        pass
    assert rec.snapshot()[0]["failing"][0] == 1


def test_recorder_loses_no_update_across_threads():
    rec = Recorder()
    n, workers = 2_000, 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n):
                with rec.span("s"):
                    pass
                rec.interval("i", 0.001)
                rec.add("c", 1)
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    spans, counters = rec.snapshot()
    assert spans["s"][0] == spans["i"][0] == n * workers
    assert counters["c"] == n * workers
    assert abs(spans["i"][1] - n * workers) < 1e-6 * n * workers


def test_engine_submit_spans_and_h2d_bytes():
    """Every phase span counts once per solved Δ_t, and the H2D counter is
    the bytes of the staged snapshot's five arrays, the frontier and f0 at
    the batch's padded shape, plus the supernode step's two rows per
    unlabelled insert."""
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-4)
    want = 0
    n = 0
    for batch, _ in gaussian_mixture_stream(SPEC):
        st = eng.step(batch)
        n += 1
        assert st.bucket != (0, 0)  # every batch of this stream solves
        u_pad, k_pad = st.bucket
        # nbr int32 + wgt f32 per slot; wl0, wl1 f32, valid bool,
        # frontier bool, f0 f32 per row
        want += u_pad * (8 * k_pad + 4 + 4 + 1 + 1 + 4)
        want += 8 * int((batch.ins_labels == UNLABELED).sum())
    spans, counters = eng.trace.snapshot()
    assert spans["engine.submit"][0] == n
    for name in SUBMIT_PHASES:
        assert spans[name][0] == n, name
    assert spans["engine.drain"][0] == spans["engine.drain.wait"][0] == n
    assert spans["engine.inflight"][0] == n
    assert counters["engine.h2d_bytes"] == want
    # the phases are children of submit: their totals fit inside its own
    inside = sum(spans[p][1] for p in SUBMIT_PHASES)
    assert inside <= spans["engine.submit"][1]


def _driven_session(svc, rng, batches, reads=True):
    tickets = []
    with svc:
        for batch in batches:
            n = len(batch.ins_emb)
            tickets.append(svc.add_points(batch.ins_emb[:n // 2],
                                          batch.ins_labels[:n // 2]))
            if reads:
                svc.query(rng.integers(0, max(1, svc.committed_view().num_nodes), 8))
            tickets.append(svc.add_points(batch.ins_emb[n // 2:],
                                          batch.ins_labels[n // 2:]))
            if len(batch.del_ids):
                tickets.append(svc.remove_points(batch.del_ids))
    return tickets


def test_service_span_counts_and_ticket_stamps():
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = LPService(StreamEngine(g, delta=1e-4), window_ops=50,
                    window_ms=5.0, max_pending_ops=100_000)
    batches = [b for b, _ in gaussian_mixture_stream(SPEC)]
    tickets = _driven_session(svc, np.random.default_rng(0), batches)
    st = svc.stats()
    assert st.batches_admitted == st.batches_committed > 1
    assert st.spans["lp.window.wait"][0] == st.batches_admitted
    assert st.spans["lp.admit"][0] == st.batches_admitted
    assert st.spans["engine.submit"][0] == st.batches_admitted
    assert st.spans["lp.ack.lag"][0] == st.batches_committed
    assert st.spans["lp.mutate.lock"][0] == st.mutations == len(tickets)
    assert st.spans["lp.read.queue"][0] == st.read_tickets > 0
    assert st.spans["lp.read.serve"][0] == st.read_batches
    for t in tickets:
        assert t.enqueued_at <= t.admitted_at <= t.committed_at
    # a ticket resolves no earlier than the drain that published its view
    assert svc.engine.last_commit_at <= max(t.committed_at for t in tickets)


def test_backpressure_relief_is_a_span(monkeypatch):
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-4)
    svc = LPService(eng, window_ops=20, window_ms=1e9, max_pending_ops=30)
    # a busy device: poll never commits, so admitted ops pin the queue
    # until the blocked writer's relief drains them
    monkeypatch.setattr(eng, "poll", lambda: None)
    batch = next(iter(gaussian_mixture_stream(SPEC)))[0]
    for lo in range(0, 60, 15):  # the third finds 30 in flight: relief
        svc.add_points(batch.ins_emb[lo:lo + 15], batch.ins_labels[lo:lo + 15])
    st = svc.stats()
    assert st.spans["lp.mutate.relieve"][0] == 1
    assert st.spans["lp.mutate.lock"][0] == st.mutations == 4
    svc.sync()


def test_profile_carries_program_spans_under_bare_names(tmp_path):
    """A profile of a small served session holds every code-region span
    the recorder counted, as host events named exactly as recorded (the
    ``batch=`` metadata does not rename them)."""
    sys.path.insert(0, str(BENCH))
    import trace_reduce

    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = LPService(StreamEngine(g, delta=1e-4), window_ops=50,
                    window_ms=5.0, max_pending_ops=100_000)
    batches = [b for b, _ in gaussian_mixture_stream(SPEC)][:3]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            _driven_session(svc, np.random.default_rng(1), batches)
    finally:
        jax.profiler.stop_trace()
    counted = {n: c for n, (c, _) in svc.stats().spans.items()
               if n not in INTERVALS}
    assert {"lp.mutate.lock", "lp.admit", "engine.submit", "engine.drain",
            "engine.drain.wait", "lp.read.serve", *SUBMIT_PHASES} <= set(counted)
    tr = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)), counted)
    seen = {}
    for _, _, name in tr.spans:
        seen[name] = seen.get(name, 0) + 1
    assert seen == counted
