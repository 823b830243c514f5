"""LP serving front-end: committed queries bit-identical to full DynLP
recompute, no torn reads while a batch is in flight, admission window,
backpressure, and the forced-8-virtual-device mesh arm (subprocess)."""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core.dynlp import DynLP
from repro.core.stream import StreamEngine
from repro.data.synth import StreamSpec, gaussian_mixture_stream
from repro.graph.dynamic import UNLABELED, DynamicGraph
from repro.serving.lp_service import Backpressure, LPService

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SPEC = StreamSpec(total_vertices=300, batch_size=60, seed=7,
                  class_sep=6.0, noise=0.9)


def _service(graph, **kw):
    eng = StreamEngine(graph, delta=1e-4)
    kw.setdefault("window_ops", 10_000)
    kw.setdefault("window_ms", 1e9)  # admission only via flush()/window
    kw.setdefault("max_pending_ops", 100_000)
    return LPService(eng, **kw)


def _split_mutations(svc, batch, parts=3):
    """Feed one stream batch as ``parts`` mutations (deletes ride on the
    first) — the coalesced window must equal the original batch."""
    n = len(batch.ins_emb)
    cuts = [(i * n) // parts for i in range(parts + 1)]
    tickets = [svc.mutate(ins_emb=batch.ins_emb[cuts[0]:cuts[1]],
                          ins_labels=batch.ins_labels[cuts[0]:cuts[1]],
                          del_ids=batch.del_ids)]
    for a, b in zip(cuts[1:], cuts[2:]):
        tickets.append(svc.mutate(ins_emb=batch.ins_emb[a:b],
                                  ins_labels=batch.ins_labels[a:b]))
    return tickets


def test_committed_queries_match_full_dynlp_recompute():
    """After every commit, the served labels are bit-identical to a full
    DynLP recompute over the same coalesced batch sequence."""
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = _service(g)
    g_ref = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    dyn = DynLP(g_ref, delta=1e-4)
    for batch, _ in gaussian_mixture_stream(SPEC):
        tickets = _split_mutations(svc, batch)
        admitted = svc.flush()
        assert len(admitted.ins_emb) == len(batch.ins_emb)
        np.testing.assert_array_equal(admitted.del_ids, batch.del_ids)
        st = svc.sync()
        assert st is not None and st.converged
        assert all(t.committed and t.latency_ms >= 0 for t in tickets)
        dyn.step(batch)

        view = svc.committed_view()
        np.testing.assert_array_equal(view.f, g_ref.f)
        np.testing.assert_array_equal(view.alive, g_ref.alive)
        # query() answers derive from the same committed state
        ids = np.flatnonzero(g_ref.alive)
        res = svc.query(ids)
        seeded = g_ref.labels[ids] != UNLABELED
        want_pred = np.where(seeded, g_ref.labels[ids],
                             (g_ref.f[ids] >= 0.5).astype(np.int8))
        want_conf = np.where(seeded, 1.0,
                             np.maximum(g_ref.f[ids], 1 - g_ref.f[ids]))
        np.testing.assert_array_equal(res.pred, want_pred)
        np.testing.assert_array_equal(res.confidence,
                                      want_conf.astype(np.float32))
        assert res.commit_id == svc.engine.commits


def test_inflight_queries_serve_previous_commit_no_torn_reads():
    """Between admission and commit the host graph is already mutated
    (new vertices appended, supernode inits written) — queries must keep
    answering from the previous committed snapshot."""
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = _service(g)
    prev_f = g.f.copy()
    prev_alive = g.alive.copy()
    for batch, _ in gaussian_mixture_stream(SPEC):
        base = g.num_nodes
        _split_mutations(svc, batch)
        svc.flush()  # admits: solve in flight, NOT committed
        assert svc.engine.in_flight
        view = svc.committed_view()
        np.testing.assert_array_equal(view.f, prev_f)
        np.testing.assert_array_equal(view.alive, prev_alive)
        # the live graph HAS already changed under the in-flight batch...
        assert g.num_nodes > base
        # ...but its new vertices don't exist for readers yet
        new_ids = np.arange(base, g.num_nodes)
        res = svc.query(new_ids)
        assert (res.pred == UNLABELED).all()
        assert (res.confidence == 0).all()
        svc.sync()
        prev_f = g.f.copy()
        prev_alive = g.alive.copy()
    assert svc.stats().queries_while_inflight > 0


def test_pipelined_windows_match_sync_per_batch():
    """Back-to-back window admissions (submit overlapping the previous
    solve, commits harvested by poll) land on the same labels as the
    one-batch-at-a-time synchronous service."""
    g_p = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    piped = _service(g_p, window_ops=SPEC.batch_size)
    g_s = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    synced = _service(g_s)
    for batch, _ in gaussian_mixture_stream(SPEC):
        # exactly one window's worth -> auto-admits inside mutate()
        piped.mutate(ins_emb=batch.ins_emb, ins_labels=batch.ins_labels,
                     del_ids=batch.del_ids)
        synced.mutate(ins_emb=batch.ins_emb, ins_labels=batch.ins_labels,
                      del_ids=batch.del_ids)
        synced.flush()
        synced.sync()
    piped.sync()
    np.testing.assert_array_equal(piped.committed_view().f,
                                  synced.committed_view().f)
    st = piped.stats()
    assert st.batches_admitted == st.batches_committed == 5
    assert st.spans["lp.mutate.lock"][0] == st.mutations


def test_admission_window_deadline_and_size():
    rng = np.random.default_rng(0)
    g = DynamicGraph(emb_dim=4, k=3)
    svc = LPService(StreamEngine(g, delta=1e-4), window_ops=8,
                    window_ms=1e9)
    # below the size bound, nothing admits
    svc.mutate(ins_emb=rng.normal(0, 1, (3, 4)).astype(np.float32),
               ins_labels=np.array([0, 1, UNLABELED], np.int8))
    assert svc.stats().batches_admitted == 0
    assert svc.stats().pending_ops == 3
    # crossing it admits immediately
    svc.mutate(ins_emb=rng.normal(0, 1, (5, 4)).astype(np.float32))
    assert svc.stats().batches_admitted == 1
    svc.sync()
    # a zero deadline admits on the next pump even for a single op
    svc.window_ms = 0.0
    svc.mutate(del_ids=np.array([0], np.int64))
    svc.pump()
    assert svc.stats().batches_admitted == 2
    svc.sync()
    assert svc.stats().pending_ops == 0


def test_backpressure_reject_and_block(monkeypatch):
    rng = np.random.default_rng(1)
    g = DynamicGraph(emb_dim=4, k=3)
    eng = StreamEngine(g, delta=1e-4)
    svc = LPService(eng, window_ops=4, window_ms=1e9, max_pending_ops=8,
                    reject_on_overload=True)
    # simulate a busy device: poll never commits, so admitted ops pin the
    # queue until an explicit drain
    monkeypatch.setattr(eng, "poll", lambda: None)
    svc.mutate(ins_emb=rng.normal(0, 1, (4, 4)).astype(np.float32),
               ins_labels=np.array([0, 1, UNLABELED, UNLABELED], np.int8))
    assert svc.stats().batches_admitted == 1  # window filled -> in flight
    svc.mutate(ins_emb=rng.normal(0, 1, (3, 4)).astype(np.float32))
    with pytest.raises(Backpressure):
        svc.mutate(ins_emb=rng.normal(0, 1, (2, 4)).astype(np.float32))
    assert svc.stats().rejected == 1
    # blocking mode sheds the same backlog by draining instead
    svc.reject_on_overload = False
    t = svc.mutate(ins_emb=rng.normal(0, 1, (2, 4)).astype(np.float32))
    assert svc.stats().pending_ops <= 8
    svc.sync()
    assert t.committed
    # a single oversized mutation can never fit -> always rejected (and
    # counted, even in blocking mode)
    with pytest.raises(Backpressure):
        svc.mutate(ins_emb=rng.normal(0, 1, (9, 4)).astype(np.float32))
    assert svc.stats().rejected == 2


def test_query_before_any_commit_and_validation():
    g = DynamicGraph(emb_dim=4, k=3)
    svc = _service(g)
    res = svc.query([0, 5, -3])
    assert (res.pred == UNLABELED).all()
    assert (res.confidence == 0).all()
    assert res.commit_id == 0
    assert svc.committed_view().num_nodes == 0
    with pytest.raises(ValueError, match="empty mutation"):
        svc.mutate()
    with pytest.raises(ValueError, match="ins_labels"):
        svc.mutate(ins_emb=np.zeros((2, 4), np.float32),
                   ins_labels=np.zeros(3, np.int8))


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core.dynlp import DynLP
    from repro.core.stream import StreamEngine
    from repro.data.synth import StreamSpec, gaussian_mixture_stream
    from repro.graph.dynamic import UNLABELED, DynamicGraph
    from repro.launch.mesh import make_stream_mesh
    from repro.serving.lp_service import LPService

    mesh = make_stream_mesh()
    assert mesh.devices.size == 8, mesh
    spec = StreamSpec(total_vertices=600, batch_size=60, seed=11,
                      class_sep=6.0, noise=0.9, frac_deleted=0.15,
                      frac_unlabeled=0.84)

    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    svc = LPService(StreamEngine(g, delta=1e-4, mesh=mesh),
                    window_ops=10_000, window_ms=1e9,
                    max_pending_ops=100_000)
    g_ref = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    dyn = DynLP(g_ref, delta=1e-4)

    prev_f = g.f.copy()
    for batch, _ in gaussian_mixture_stream(spec):
        svc.mutate(ins_emb=batch.ins_emb, ins_labels=batch.ins_labels,
                   del_ids=batch.del_ids)
        svc.flush()
        # in-flight on the mesh: readers still see the previous commit
        assert svc.engine.in_flight
        np.testing.assert_array_equal(svc.committed_view().f, prev_f)
        svc.sync()
        dyn.step(batch)
        # committed labels bit-identical to the full DynLP recompute,
        # row-sharded over the 8-device mesh
        np.testing.assert_array_equal(svc.committed_view().f, g_ref.f)
        prev_f = g.f.copy()
    st = svc.stats()
    assert st.recompiles <= st.bucket_rungs, (st.recompiles, st.bucket_rungs)
    assert svc.engine.plan_builds == st.bucket_rungs
    print("OK lp-service-8dev", st.batches_committed, "commits",
          st.recompiles, "recompiles")
""")


def test_lp_service_sharded_bit_identical_8dev():
    """Service on a forced 8-virtual-device mesh: committed queries stay
    bit-identical to the single-device DynLP recompute, in-flight reads
    still serve the previous commit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=SRC)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK lp-service-8dev" in out.stdout


def test_service_stats_counts():
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = _service(g)
    for batch, _ in gaussian_mixture_stream(SPEC):
        svc.mutate(ins_emb=batch.ins_emb, ins_labels=batch.ins_labels,
                   del_ids=batch.del_ids)
        svc.flush()
        svc.query(np.arange(4))
        svc.sync()
    st = svc.stats()
    assert st.mutations == 5 and st.batches_committed == 5
    assert st.queries == 5 and st.query_nodes == 20
    assert st.queries_while_inflight == 5
    assert st.pending_ops == 0 and st.rejected == 0
    assert st.spans["lp.ack.lag"][0] == 5
    assert st.spans["engine.submit"][0] == st.spans["engine.drain"][0] == 5
    assert st.recompiles <= st.bucket_rungs


@pytest.mark.parametrize("past", [0, 1, 1_000])
def test_relabel_past_last_row_is_dropped_and_driver_lives(past):
    """A relabel of an id that no row has yet is dropped like an
    out-of-range delete; it must not kill the driver that admits it."""
    g = DynamicGraph(emb_dim=SPEC.emb_dim, k=5)
    svc = _service(g, window_ms=1.0)
    batch = next(iter(gaussian_mixture_stream(SPEC)))[0]
    svc.add_points(batch.ins_emb, batch.ins_labels)
    svc.sync()
    labels = g.labels.copy()
    with svc:
        t = svc.relabel([g.num_nodes + past, 0], [1, 1])
        deadline = time.perf_counter() + 60
        while not t.committed and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert t.committed and svc.driver_running
    labels[0] = 1
    np.testing.assert_array_equal(g.labels, labels)


COMMIT_SPEC = StreamSpec(total_vertices=300, batch_size=60, seed=7,
                         class_sep=6.0, noise=0.9, frac_unlabeled=0.8,
                         frac_labeled=0.11)


@pytest.mark.parametrize("ingest,inserts", [("device", True),
                                            ("device", False),
                                            ("host", True)])
def test_commit_order_matches_step_stream(monkeypatch, ingest, inserts):
    """An admit whose staging reads back from the device (inserts, device
    ingest) commits the in-flight batch first: window t's tickets hold
    commit t before window t+1's submit starts, and every commit's view
    is byte-identical to a ``StreamEngine.step`` twin over the same
    coalesced batches.  Windows without inserts and host ingest keep the
    old order: the commit lands inside submit, ``lp.commit.early`` stays
    0, and the views are the same."""
    rng = np.random.default_rng(5)
    g = DynamicGraph(emb_dim=COMMIT_SPEC.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-4, ingest=ingest)
    svc = LPService(eng, window_ops=10_000, window_ms=1e9,
                    max_pending_ops=100_000)
    twin = StreamEngine(DynamicGraph(emb_dim=COMMIT_SPEC.emb_dim, k=5),
                        delta=1e-4, ingest=ingest)
    # a busy device: poll never commits, so every admit below finds the
    # previous window's solve in flight
    monkeypatch.setattr(eng, "poll", lambda: None)
    views, twin_views = {}, {}
    drain = eng.drain

    def recording_drain():
        st = drain()
        if st is not None:
            views[eng.committed_view().commit_id] = eng.committed_view()
        return st

    monkeypatch.setattr(eng, "drain", recording_drain)
    prev_tickets, at_submit = [], []
    submit = eng.submit

    def recording_submit(batch):
        at_submit.append([(t.committed, t.commit_id) for t in prev_tickets])
        return submit(batch)

    monkeypatch.setattr(eng, "submit", recording_submit)

    stream = [b for b, _ in gaussian_mixture_stream(COMMIT_SPEC)]
    for t, batch in enumerate(stream, start=1):
        tickets = []
        if inserts or t == 1:
            tickets = _split_mutations(svc, batch, parts=2)
        else:  # deletes alone, beside the relabels below
            tickets = [svc.remove_points(batch.del_ids)]
        if t > 1:
            # relabel rows the in-flight batch is solving (seeding them)
            # and retract seeds (their f resets to 0.5): later relabels win
            unl = np.flatnonzero(g.alive & (g.labels == UNLABELED))
            seeds = np.flatnonzero(g.alive & (g.labels != UNLABELED))
            ids = np.concatenate([rng.choice(unl, 6, replace=False),
                                  rng.choice(seeds, 2, replace=False)])
            labs = np.concatenate([rng.integers(0, 2, 6),
                                   np.full(2, UNLABELED)]).astype(np.int8)
            tickets.append(svc.relabel(ids, labs))
        admitted = svc.flush()
        twin.step(admitted)
        twin_views[t] = twin.committed_view()
        # window t-1 committed, here or inside submit, as commit t-1
        assert all(q.commit_id == t - 1 for q in prev_tickets)
        prev_tickets = tickets
    svc.sync()
    assert all(q.commit_id == len(stream) for q in prev_tickets)

    early = ingest == "device" and inserts
    n_early = svc.stats().counters.get("lp.commit.early", 0)
    assert n_early == (len(stream) - 1 if early else 0)
    # at submit of window t+1, window t has committed iff the rule engaged
    assert at_submit[0] == []
    for t, seen in enumerate(at_submit[1:], start=1):
        want = (True, t) if early else (False, None)
        assert seen and all(s == want for s in seen), (t, seen)

    assert sorted(views) == sorted(twin_views) == list(
        range(1, len(stream) + 1))
    for c, view in views.items():
        for name in ("f", "labels", "alive"):
            np.testing.assert_array_equal(getattr(view, name),
                                          getattr(twin_views[c], name),
                                          err_msg=f"commit {c} {name}")
    for name in ("f", "labels", "alive", "knn_idx", "src", "wgt"):
        np.testing.assert_array_equal(getattr(g, name),
                                      getattr(twin.graph, name))
