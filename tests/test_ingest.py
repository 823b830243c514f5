"""Device-resident ingest: property tests against the host oracle.

Core claims (ISSUE 7 acceptance):

  * incremental device kNN over random insert streams is bit-identical
    to rebuilding with the host ``build_knn_graph`` oracle (CSR arrays
    compared raw, no canonicalization) — incl. displaced-edge deletes,
    empty and singleton batches;
  * mixed insert/delete streams through the device selector match the
    host staging selector batch-for-batch (lists, edges, labels);
  * ``LPService.add_points`` over a device-ingest engine produces labels
    bit-identical to the host-kNN ``BatchUpdate`` path on a 50-batch
    mixed stream — single-device here, forced 8-virtual-device mesh in
    the subprocess arm;
  * the ingest jit cache stays within the a-priori ladder bound.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.stream import StreamEngine
from repro.graph.dynamic import UNLABELED, BatchUpdate, DynamicGraph
from repro.graph.knn import build_knn_graph
from repro.ingest import DeviceIngestor, ingest_cache_size, \
    ingest_ladder_bound
from repro.ingest.embedding_store import EmbeddingStore, cap_bucket, dim_pad
from repro.serving.lp_service import LPService

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def _insert_stream(rng, emb_dim, n_batches, max_batch):
    sizes = [int(rng.integers(0, max_batch + 1)) for _ in range(n_batches)]
    sizes[0] = max(sizes[0], 3)
    sizes[min(1, n_batches - 1)] = 1  # force a singleton batch
    if n_batches > 2:
        sizes[2] = 0  # force an empty batch
    return [rng.normal(size=(s, emb_dim)).astype(np.float32) for s in sizes]


def _apply(g, emb, dels, selector):
    g.apply_batch(BatchUpdate(
        ins_emb=emb, ins_labels=np.full(len(emb), UNLABELED, np.int8),
        del_ids=dels), selector=selector)


@given(st.integers(0, 10_000), st.integers(3, 8), st.integers(2, 6),
       st.integers(4, 32))
@settings(max_examples=8, deadline=None)
def test_device_insert_stream_bit_identical_to_rebuild(
        seed, n_batches, k, emb_dim):
    """Random insert streams (empty + singleton batches included): the
    device-ingested graph's CSR snapshot equals a from-scratch host
    ``build_knn_graph`` bit for bit."""
    rng = np.random.default_rng(seed)
    batches = _insert_stream(rng, emb_dim, n_batches, 24)
    g = DynamicGraph(emb_dim, k=k)
    ing = DeviceIngestor(emb_dim)
    for b in batches:
        _apply(g, b, np.zeros(0, np.int64), ing)
    ref = build_knn_graph(np.concatenate(batches), k=k)
    csr, ids = g.snapshot_csr()
    np.testing.assert_array_equal(ids, np.arange(g.num_nodes))
    np.testing.assert_array_equal(csr.rowptr, ref.rowptr)
    np.testing.assert_array_equal(csr.col, ref.col)
    np.testing.assert_array_equal(csr.wgt, ref.wgt)


@given(st.integers(0, 10_000), st.integers(3, 7), st.integers(2, 5),
       st.floats(0.0, 0.3))
@settings(max_examples=8, deadline=None)
def test_device_matches_host_selector_mixed_stream(
        seed, n_batches, k, frac_del):
    """Mixed insert/delete streams: device selector == host selector
    batch-for-batch on lists AND the undirected edge arrays (the
    displaced-edge delete path is exercised by every hole refill)."""
    rng = np.random.default_rng(seed)
    emb_dim = 12
    batches = _insert_stream(rng, emb_dim, n_batches, 20)
    gh = DynamicGraph(emb_dim, k=k)
    gd = DynamicGraph(emb_dim, k=k)
    ing = DeviceIngestor(emb_dim)
    total = 0
    for b in batches:
        n_del = int(round(frac_del * len(b))) if total else 0
        dels = (rng.choice(total, size=min(n_del, total), replace=False)
                .astype(np.int64) if n_del else np.zeros(0, np.int64))
        _apply(gh, b, dels, None)
        _apply(gd, b, dels, ing)
        total += len(b)
        np.testing.assert_array_equal(gh.knn_idx, gd.knn_idx)
        np.testing.assert_array_equal(gh.knn_wgt, gd.knn_wgt)
        np.testing.assert_array_equal(gh.src, gd.src)
        np.testing.assert_array_equal(gh.dst, gd.dst)
        np.testing.assert_array_equal(gh.wgt, gd.wgt)


def test_mass_duplicates_tie_break():
    """All-identical points: deep weight ties must resolve to the same
    lowest-id neighbors on both paths."""
    dup = np.ones((20, 6), np.float32)
    gh = DynamicGraph(6, k=3)
    gd = DynamicGraph(6, k=3)
    ing = DeviceIngestor(6)
    for lo, hi in [(0, 9), (9, 20)]:
        _apply(gh, dup[lo:hi], np.zeros(0, np.int64), None)
        _apply(gd, dup[lo:hi], np.zeros(0, np.int64), ing)
    np.testing.assert_array_equal(gh.knn_idx, gd.knn_idx)
    np.testing.assert_array_equal(gh.knn_wgt, gd.knn_wgt)


def _mixed_service_stream(ingest, mesh=None, n_batches=50, seed=123):
    """Drive a service with 50 typed mixed mutations; returns the
    committed f after every sync plus the final graph."""
    rng = np.random.default_rng(seed)
    emb_dim, k = 10, 4
    g = DynamicGraph(emb_dim, k=k)
    eng = StreamEngine(g, delta=1e-4, ingest=ingest, mesh=mesh)
    svc = LPService(eng, window_ops=64, window_ms=1e9, max_pending_ops=4096)
    total = 0
    outs = []
    for t in range(n_batches):
        m = int(rng.integers(1, 10))
        cls = rng.integers(0, 2, m).astype(np.int8)
        emb = np.zeros((m, emb_dim), np.float32)
        emb[:, 0] = np.where(cls == 1, 3.0, -3.0)
        emb += rng.normal(0, 0.9, (m, emb_dim)).astype(np.float32)
        labels = np.where(rng.random(m) < 0.2, cls, UNLABELED).astype(np.int8)
        if t == 0:
            labels[0], cls[0] = 0, 0
            emb[0, 0] = -3.0
        svc.add_points(emb, labels)
        total += m
        if t % 5 == 4 and total > 8:
            svc.remove_points(
                rng.choice(total, size=3, replace=False).astype(np.int64))
        svc.sync()
        outs.append(g.f.copy())
    return outs, g


def test_service_add_points_device_bit_identical_to_host_50_batches():
    """Acceptance: 50-batch mixed insert/delete ``add_points`` stream —
    device-ingest labels bit-identical to the host-kNN path after every
    commit."""
    oh, gh = _mixed_service_stream("host")
    od, gd = _mixed_service_stream("device")
    assert len(oh) == len(od) == 50
    for i, (fh, fd) in enumerate(zip(oh, od)):
        np.testing.assert_array_equal(fh, fd, err_msg=f"batch {i}")
    np.testing.assert_array_equal(gh.knn_idx, gd.knn_idx)
    np.testing.assert_array_equal(gh.labels, gd.labels)
    np.testing.assert_array_equal(gh.alive, gd.alive)


SCRIPT_8DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, {src!r})
    from repro.launch.mesh import make_stream_mesh
    import numpy as np
    sys.path.insert(0, {tests!r})
    from test_ingest import _mixed_service_stream

    mesh = make_stream_mesh()
    assert mesh.devices.size == 8, mesh
    oh, gh = _mixed_service_stream("host", mesh=mesh)
    od, gd = _mixed_service_stream("device", mesh=mesh)
    for i, (fh, fd) in enumerate(zip(oh, od)):
        np.testing.assert_array_equal(fh, fd, err_msg=f"batch {{i}}")
    np.testing.assert_array_equal(gh.knn_idx, gd.knn_idx)
    print("OK ingest-8dev", len(oh), "commits")
""")


def test_service_add_points_device_bit_identical_8dev():
    """Acceptance: the same 50-batch stream on a forced 8-virtual-device
    mesh (subprocess, same pattern as tests/test_stream_sharded.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_8DEV.format(
            src=os.path.abspath(SRC),
            tests=os.path.dirname(os.path.abspath(__file__)))],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK ingest-8dev" in out.stdout


# --------------------------------------------------------------------- #
# embedding store unit behavior
# --------------------------------------------------------------------- #
def test_store_ladder_growth_and_padding():
    store = EmbeddingStore(emb_dim=10)
    assert store.dp == dim_pad(10) == 16
    assert store.capacity == cap_bucket(1) == 1024
    rng = np.random.default_rng(0)
    store.append(rng.normal(size=(700, 10)).astype(np.float32))
    assert store.capacity == 1024 and store.grows == 0
    store.append(rng.normal(size=(700, 10)).astype(np.float32))
    assert store.capacity == 2048 and store.grows == 1
    assert store.count == 1400
    v = np.asarray(store.valid)
    assert v[:1400].all() and not v[1400:].any()
    # padded feature columns are zero (inert under dot products)
    e = np.asarray(store.emb)
    assert (e[:, 10:] == 0).all()


def test_store_kill_and_kth_roundtrip():
    store = EmbeddingStore(emb_dim=4)
    rng = np.random.default_rng(1)
    store.append(rng.normal(size=(50, 4)).astype(np.float32))
    store.kill(np.array([3, 7, 11], np.int64))
    v = np.asarray(store.valid)
    assert not v[[3, 7, 11]].any() and v[:50].sum() == 47
    store.set_kth(np.array([5, 9], np.int64),
                  np.array([0.25, 0.75], np.float32))
    kth = np.asarray(store.kth)
    assert kth[5] == np.float32(0.25) and kth[9] == np.float32(0.75)


def test_ingest_cache_within_ladder_bound():
    """One fixed-shape stream: live jit entries stay under the a-priori
    ladder bound (the bench ``--check`` recompile gate)."""
    rng = np.random.default_rng(2)
    emb_dim, k = 16, 4
    g = DynamicGraph(emb_dim, k=k)
    ing = DeviceIngestor(emb_dim)
    c0 = ingest_cache_size()
    total = 0
    for t in range(30):
        m = int(rng.integers(1, 33))
        dels = (rng.choice(total, size=4, replace=False).astype(np.int64)
                if t % 6 == 5 and total > 8 else np.zeros(0, np.int64))
        _apply(g, rng.normal(size=(m, emb_dim)).astype(np.float32), dels, ing)
        total += m
    assert ingest_cache_size() - c0 <= ingest_ladder_bound(total, 32)


def test_ingestor_out_of_sync_raises():
    g1 = DynamicGraph(6, k=3)
    g2 = DynamicGraph(6, k=3)
    ing = DeviceIngestor(6)
    rng = np.random.default_rng(4)
    _apply(g1, rng.normal(size=(5, 6)).astype(np.float32),
           np.zeros(0, np.int64), ing)
    _apply(g2, rng.normal(size=(3, 6)).astype(np.float32),
           np.zeros(0, np.int64), None)
    try:
        # same ingestor on a different stream: row counts disagree
        _apply(g2, rng.normal(size=(4, 6)).astype(np.float32),
               np.zeros(0, np.int64), ing)
    except RuntimeError as e:
        assert "out of sync" in str(e)
    else:  # pragma: no cover
        raise AssertionError("expected out-of-sync RuntimeError")
