"""Label-propagation serving front-end on the streaming engine.

    PYTHONPATH=src python examples/serve_lp.py

0. Quickstart: the sklearn-style ``DynLabelPropagation`` estimator —
   ``fit`` / ``partial_fit`` / ``predict`` over raw embeddings; the
   whole graph/engine/service stack is derived for you (the recommended
   front door; everything below peels a layer off it).
1. Stands up an ``LPService`` over a ``StreamEngine`` and feeds it mixed
   traffic: mutations via the typed embedding-first entry points
   (``add_points`` / ``remove_points`` — callers never build edge
   lists) coalesced per admission window, query bursts answered from
   the last committed snapshot.
2. Shows the consistency contract: while a batch's solve is in flight
   the service keeps answering from the previous commit (its new
   vertices "don't exist yet"); after ``sync()`` the same query sees
   them labeled — read-your-writes.
3. Shows backpressure: a service with a tiny queue bound configured to
   reject sheds mutations with ``Backpressure`` instead of queueing
   without bound.
4. Shows the async driver (``with svc:``): admission deadlines fire
   with zero caller traffic, concurrent readers' tickets fuse into one
   jitted device gather, and ``close()`` drains everything on exit.
"""

import numpy as np

from repro.core.stream import StreamEngine
from repro.data.synth import StreamSpec, gaussian_mixture_stream
from repro.graph.dynamic import UNLABELED, DynamicGraph
from repro.serving.estimator import DynLabelPropagation
from repro.serving.lp_service import Backpressure, LPService


def estimator_quickstart():
    """Two moons of gaussians, three labeled points per class, the rest
    inferred — then stream more points in with ``partial_fit``."""
    rng = np.random.default_rng(0)
    n = 200
    X = np.concatenate([rng.normal(-2, 0.7, (n // 2, 8)),
                        rng.normal(+2, 0.7, (n // 2, 8))]).astype(np.float32)
    truth = np.repeat([0, 1], n // 2).astype(np.int8)
    y = np.full(n, UNLABELED, np.int8)
    y[[0, 1, 2, n - 3, n - 2, n - 1]] = truth[[0, 1, 2, n - 3, n - 2, n - 1]]

    clf = DynLabelPropagation(k=5).fit(X, y)
    acc = (clf.transduction_ == truth).mean()
    Xq = np.concatenate([rng.normal(-2, 0.7, (20, 8)),
                         rng.normal(+2, 0.7, (20, 8))]).astype(np.float32)
    pred = clf.predict(Xq)  # inductive: unseen embeddings
    clf.partial_fit(Xq, np.full(len(Xq), UNLABELED, np.int8))  # stream in
    print(f"estimator quickstart: transductive acc {acc:.3f} with "
          f"{int((y != UNLABELED).sum())}/{n} seeds; predict() labeled "
          f"{len(pred)} unseen points; graph now {clf.graph_.num_alive} "
          f"vertices after partial_fit\n")


def _mean_ms(spans: dict, name: str) -> float:
    """Mean milliseconds of a span from ``ServiceStats.spans``."""
    count, total_ms = spans[name]
    return total_ms / count


def serving_demo():
    spec = StreamSpec(total_vertices=900, batch_size=60, seed=0,
                      class_sep=6.0, noise=0.9)
    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    svc = LPService(StreamEngine(g, delta=1e-4),
                    window_ops=2 * spec.batch_size, window_ms=1e9,
                    max_pending_ops=16 * spec.batch_size)
    rng = np.random.default_rng(1)
    for batch, _ in gaussian_mixture_stream(spec):
        base = g.num_nodes
        # each stream batch arrives as a few typed mutations in one
        # window — embedding-first: the service derives the graph delta
        n = len(batch.ins_emb)
        svc.add_points(batch.ins_emb[:n // 2], batch.ins_labels[:n // 2])
        if len(batch.del_ids):
            svc.remove_points(batch.del_ids)
        svc.add_points(batch.ins_emb[n // 2:], batch.ins_labels[n // 2:])
        svc.flush()  # admit: the solve is now in flight

        # reads never block on the in-flight solve — this batch's
        # vertices are invisible until it commits
        probe = np.arange(base, min(base + 3, g.num_nodes))
        r = svc.query(probe)
        assert (r.pred == UNLABELED).all() and (r.confidence == 0).all()
        burst = rng.integers(0, max(1, svc.committed_view().num_nodes), 64)
        svc.query(burst)

        svc.sync()  # read-your-writes from here on
        r = svc.query(probe)
        assert (r.confidence > 0).all()
    st = svc.stats()
    print(f"served {st.queries} query calls ({st.query_nodes} node lookups, "
          f"{st.queries_while_inflight} mid-flight) against "
          f"{st.mutations} mutations in {st.batches_committed} windows | "
          f"write-lock wait {_mean_ms(st.spans, 'lp.mutate.lock'):.2f} ms, "
          f"window open {_mean_ms(st.spans, 'lp.window.wait'):.1f} ms, "
          f"submit {_mean_ms(st.spans, 'engine.submit'):.1f} ms (means) | "
          f"{st.recompiles} recompiles over {st.bucket_rungs} bucket rungs\n")


def backpressure_demo():
    rng = np.random.default_rng(2)
    g = DynamicGraph(emb_dim=8, k=3)
    svc = LPService(StreamEngine(g, delta=1e-4), window_ops=32,
                    window_ms=1e9, max_pending_ops=64,
                    reject_on_overload=True)
    accepted = 0
    for _ in range(8):  # normal traffic fits the queue bound
        svc.add_points(rng.normal(0, 1, (8, 8)).astype(np.float32))
        accepted += 1
    try:  # a request that can never fit is shed, not queued forever
        svc.add_points(rng.normal(0, 1, (100, 8)).astype(np.float32))
        raise AssertionError("oversized mutation was not shed")
    except Backpressure as e:
        shed = str(e)
    svc.sync()
    print(f"backpressure: {accepted} mutations accepted, oversized one "
          f"shed ('{shed}'); {svc.stats().batches_committed} windows "
          f"committed")


def async_driver_demo():
    """The background driver clocks the service: deadlines fire without
    caller traffic and concurrent reads batch into fused gathers."""
    rng = np.random.default_rng(3)
    g = DynamicGraph(emb_dim=8, k=3)
    svc = LPService(StreamEngine(g, delta=1e-4),
                    window_ops=1000, window_ms=20.0)
    with svc:  # start() the driver; close() on exit drains everything
        t = svc.add_points(rng.normal(0, 1, (12, 8)).astype(np.float32),
                           (np.arange(12) % 2).astype(np.int8))
        # far below window_ops and we never call pump(): only the
        # driver's deadline clock can admit this window
        while not t.committed:
            pass
        tickets = [svc.query_async(rng.integers(0, 12, 16))
                   for _ in range(32)]
        results = [tk.wait(30.0) for tk in tickets]
        assert all((r.confidence > 0).all() for r in results)
        st = svc.stats()
    print(f"async driver: window deadline-admitted with zero caller "
          f"traffic ({st.deadline_admissions} deadline admissions); "
          f"{st.read_tickets} read tickets served by {st.read_batches} "
          f"fused device gathers")


if __name__ == "__main__":
    estimator_quickstart()
    serving_demo()
    backpressure_demo()
    async_driver_demo()
