"""Async serving driver: deadlines fire with zero caller traffic, fused
reads are never torn across a mid-burst commit, shutdown drains every
in-flight ticket, and the forced-8-virtual-device benchmark keeps the
sharded read path at parity with single-device (subprocess)."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.stream import StreamEngine
from repro.graph.dynamic import UNLABELED, DynamicGraph
from repro.serving.lp_service import LPService

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                     "benchmarks"))

RNG = np.random.default_rng(0)


def _service(**kw):
    g = DynamicGraph(emb_dim=8, k=4)
    kw.setdefault("window_ops", 64)
    kw.setdefault("window_ms", 15.0)
    return LPService(StreamEngine(g, delta=1e-3), **kw)


def _labeled(n, base=0):
    """n vertices with the deterministic label pattern (i + base) % 2."""
    emb = RNG.normal(size=(n, 8)).astype(np.float32)
    lab = ((np.arange(n) + base) % 2).astype(np.int8)
    return emb, lab


def _wait_until(cond, timeout=20.0, msg="condition"):
    t0 = time.perf_counter()
    while not cond():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.005)


def test_deadline_fires_with_zero_caller_traffic():
    """One small mutation, then NO further calls: the driver's clock must
    close the window at its deadline and commit the batch on its own."""
    svc = _service(window_ops=1000, window_ms=25.0)
    with svc:
        t = svc.mutate(*_labeled(4))
        assert not t.committed  # window open, far below the size bound
        _wait_until(lambda: t.committed, msg="deadline admission + commit")
        st = svc.stats()
        assert st.deadline_admissions >= 1
        assert st.batches_admitted == st.batches_committed == 1
    # the committed labels are visible to a plain read afterwards
    r = svc.query(np.arange(4))
    assert (r.pred >= 0).all() and (r.confidence == 1.0).all()


def test_driver_failure_surfaces_to_writers():
    """A tick that raises ends the driver's loop; the exception reaches
    the next writer and ``stop`` instead of dying with the thread."""
    svc = _service(window_ops=1000, window_ms=5.0)

    def broken_submit(batch):
        raise ValueError("solve failed")

    svc.engine.submit = broken_submit
    svc.start()
    driver = svc._driver
    svc.mutate(*_labeled(4))  # below the size bound: the deadline admits
    _wait_until(lambda: driver.error is not None, msg="driver failure")
    assert not driver.is_alive()
    with pytest.raises(RuntimeError, match="service driver failed") as e:
        svc.sync()
    assert isinstance(e.value.__cause__, ValueError)
    with pytest.raises(RuntimeError, match="service driver failed"):
        svc.stop()


def test_concurrent_readers_never_torn_across_commits():
    """Reader threads hammer the service while commits land mid-burst.

    Seeds are inserted in id order with a deterministic label pattern,
    and one admission window inserts a contiguous id block atomically —
    so every coherent view knows a PREFIX of the inserted ids.  A torn
    read (mixing two views in one result) would answer a high id while
    a lower id still reads UNLABELED, or return a wrong label."""
    svc = _service(window_ops=8, window_ms=2.0)
    total = 160
    stop = threading.Event()
    failures: list[str] = []

    def reader():
        ids = np.arange(total)
        while not stop.is_set():
            r = svc.query(ids)
            known = r.pred != UNLABELED
            if known.any():
                k = int(np.flatnonzero(known).max()) + 1
                if not known[:k].all():
                    failures.append(f"non-prefix visibility at commit "
                                    f"{r.commit_id}")
                    return
                expect = (np.arange(k) % 2).astype(np.int8)
                if not np.array_equal(r.pred[:k], expect):
                    failures.append(f"wrong labels at commit {r.commit_id}")
                    return
                if not (r.confidence[:k] == 1.0).all():
                    failures.append("seed confidence != 1.0")
                    return
            if not (r.confidence[~known] == 0.0).all():
                failures.append("unknown ids with nonzero confidence")
                return

    with svc:
        threads = [threading.Thread(target=reader) for _ in range(4)]
        for th in threads:
            th.start()
        done = 0
        while done < total:
            n = min(8, total - done)
            svc.mutate(*_labeled(n, base=done))
            done += n
            time.sleep(0.002)  # let commits interleave with read bursts
        svc.sync()
        stop.set()
        for th in threads:
            th.join(20.0)
    assert not failures, failures
    r = svc.query(np.arange(total))
    assert (r.pred != UNLABELED).all()  # everything committed in the end


def test_stop_drains_inflight_tickets():
    """Every ticket queued before stop() is fulfilled, not abandoned."""
    svc = _service()
    with svc:
        svc.mutate(*_labeled(16))
        svc.sync()
        tickets = [svc.query_async(RNG.integers(0, 16, 32))
                   for _ in range(64)]
    # context exit ran close() -> stop(): all tickets must be done
    assert all(t.done for t in tickets)
    results = [t.wait(0.1) for t in tickets]
    assert all(r.pred.shape == (32,) for r in results)
    assert not svc.driver_running


def test_reads_batch_across_concurrent_callers():
    """Concurrent async reads fuse: fewer device gathers than tickets."""
    svc = _service()
    with svc:
        svc.mutate(*_labeled(32))
        svc.sync()
        tickets = [svc.query_async(RNG.integers(0, 32, 16))
                   for _ in range(100)]
        for t in tickets:
            t.wait(30.0)
        st = svc.stats()
        assert st.read_tickets == 100
        assert st.read_batches < st.read_tickets  # fusion happened
        assert st.queries == 100  # each ticket still counts as one query


def test_async_results_match_host_view_semantics():
    """Fused device gathers answer exactly like ``LabelView.query`` —
    including dead, unknown and out-of-range ids."""
    svc = _service()
    with svc:
        svc.mutate(*_labeled(24))
        svc.mutate(ins_emb=RNG.normal(size=(8, 8)).astype(np.float32))
        svc.sync()
        svc.mutate(del_ids=np.arange(3))
        svc.sync()
        ids = np.array([-5, 0, 1, 2, 5, 23, 24, 30, 31, 32, 10**6])
        got = svc.query(ids, cutoff=0.4)
    want_pred, want_conf = svc.committed_view().query(ids, cutoff=0.4)
    np.testing.assert_array_equal(got.pred, want_pred)
    np.testing.assert_allclose(got.confidence, want_conf)


def test_driver_lifecycle_idempotent_and_restartable():
    svc = _service()
    svc.start()
    svc.start()  # idempotent
    assert svc.driver_running
    svc.stop()
    assert not svc.driver_running
    svc.start()  # restart after stop
    svc.mutate(*_labeled(4))
    svc.sync()
    assert svc.query(np.arange(4)).pred.shape == (4,)
    svc.close()
    assert not svc.driver_running


@pytest.mark.slow
def test_sharded_reads_keep_pace_with_single_device_8dev():
    """The --tiny benchmark under a forced 8-virtual-device mesh: the
    sharded arm's saturated read rate must clear the recorded ratio
    floor against single-device (the PR-5 regression was 0.47x), and
    both arms must clear the 100x lookup floor — the full --check gate
    set, which includes both bounds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_FORCE_HOST_DEVICES="8",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "serve_lp.py"),
         "--tiny", "--check", "--out", "/tmp/BENCH_serve_test.json"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "serve_sharded" in out.stdout
