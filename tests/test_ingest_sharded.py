"""Mesh-sharded EmbeddingStore: property tests against the single-device
store (ISSUE 10 acceptance).

Core claims:

  * the row-sharded store + move-the-batch sweep yields graphs AND
    displaced-row (``flagged``) sets bit-identical to the single-device
    store, batch for batch, over mixed insert/delete streams — checked
    in-process on a 1-device mesh (hypothesis-driven) and over 50 mixed
    batches on a forced 8-virtual-device mesh (subprocess);
  * per-device store bytes on the 8-device mesh are exactly 1/8 of the
    single-device store's, and the jit cache stays within
    ``ingest_ladder_bound(..., sharded=True)``;
  * checkpoints are mesh-independent both ways: a sharded(8-dev) engine
    restores mesh-less and a mesh-less engine restores sharded(8-dev),
    each continues streaming, and final labels stay bit-identical to an
    uninterrupted oracle (extends the PR-8 elastic-restore contract).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.graph.dynamic import UNLABELED, BatchUpdate, DynamicGraph
from repro.ingest import DeviceIngestor
from repro.launch.mesh import make_stream_mesh

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
TESTS = os.path.dirname(os.path.abspath(__file__))


class RecordingIngestor(DeviceIngestor):
    """DeviceIngestor that records each batch's displaced-row set."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.flagged_log = []

    def select(self, g, new_ids, embn_new):
        sel = super().select(g, new_ids, embn_new)
        self.flagged_log.append(np.sort(sel.flagged))
        return sel


def _mixed_batches(rng, emb_dim, n_batches, max_batch):
    sizes = [int(rng.integers(1, max_batch + 1)) for _ in range(n_batches)]
    return [rng.normal(size=(s, emb_dim)).astype(np.float32) for s in sizes]


def _apply(g, emb, dels, selector):
    g.apply_batch(BatchUpdate(
        ins_emb=emb, ins_labels=np.full(len(emb), UNLABELED, np.int8),
        del_ids=dels), selector=selector)


def run_sharded_vs_single(mesh, n_batches, seed, emb_dim=12, k=4,
                          frac_del=0.15, max_batch=20):
    """Drive a sharded and a single-device ingest stream over the same
    mixed batches; assert graphs and flagged sets bit-identical after
    every batch.  Returns (sharded ingestor, single ingestor, total rows,
    max batch size) for callers that gate memory/cache on top."""
    rng = np.random.default_rng(seed)
    batches = _mixed_batches(rng, emb_dim, n_batches, max_batch)
    gs = DynamicGraph(emb_dim, k=k)
    g1 = DynamicGraph(emb_dim, k=k)
    ing_s = RecordingIngestor(emb_dim, mesh=mesh)
    ing_1 = RecordingIngestor(emb_dim)
    assert ing_s.store.n_shards == int(mesh.devices.size)
    assert ing_1.store.n_shards == 1
    total = 0
    for t, b in enumerate(batches):
        n_del = int(round(frac_del * len(b))) if total else 0
        dels = (rng.choice(total, size=min(n_del, total), replace=False)
                .astype(np.int64) if n_del else np.zeros(0, np.int64))
        _apply(gs, b, dels, ing_s)
        _apply(g1, b, dels, ing_1)
        total += len(b)
        np.testing.assert_array_equal(gs.knn_idx, g1.knn_idx,
                                      err_msg=f"batch {t}")
        np.testing.assert_array_equal(gs.knn_wgt, g1.knn_wgt,
                                      err_msg=f"batch {t}")
        np.testing.assert_array_equal(gs.src, g1.src)
        np.testing.assert_array_equal(gs.dst, g1.dst)
        np.testing.assert_array_equal(gs.wgt, g1.wgt)
        np.testing.assert_array_equal(
            ing_s.flagged_log[-1], ing_1.flagged_log[-1],
            err_msg=f"flagged sets diverge at batch {t}")
    return ing_s, ing_1, total, max_batch


@given(st.integers(0, 10_000), st.integers(3, 8), st.floats(0.0, 0.3))
@settings(max_examples=6, deadline=None)
def test_sharded_store_bit_identical_1dev_mesh(seed, n_batches, frac_del):
    """Property: on a 1-device mesh the sharded path (shard_map sweep,
    sharded update jits, merge reduction) is still bit-identical to the
    plain single-device store — graphs and flagged sets alike."""
    run_sharded_vs_single(make_stream_mesh(1), n_batches, seed,
                          frac_del=frac_del)


def test_sharded_store_duplicate_ties_cross_shard():
    """All-identical points spanning every shard: the merge reduction
    must resolve deep weight ties to the same lowest-global-id neighbors
    the single-device top-k picks."""
    mesh = make_stream_mesh(1)
    dup = np.ones((24, 6), np.float32)
    gs, g1 = DynamicGraph(6, k=3), DynamicGraph(6, k=3)
    ing_s, ing_1 = DeviceIngestor(6, mesh=mesh), DeviceIngestor(6)
    for lo, hi in [(0, 11), (11, 24)]:
        _apply(gs, dup[lo:hi], np.zeros(0, np.int64), ing_s)
        _apply(g1, dup[lo:hi], np.zeros(0, np.int64), ing_1)
    np.testing.assert_array_equal(gs.knn_idx, g1.knn_idx)
    np.testing.assert_array_equal(gs.knn_wgt, g1.knn_wgt)


def test_indivisible_mesh_falls_back_with_warning():
    """A mesh whose device count cannot divide the capacity ladder falls
    back to the single-device store loudly, not wrongly."""
    import warnings

    class FakeMesh:
        class devices:
            size = 7
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ing = DeviceIngestor(8, mesh=FakeMesh())
    assert ing.mesh is None and ing.store.n_shards == 1
    assert any("does not" in str(x.message) for x in w)


# --------------------------------------------------------------------- #
# forced 8-virtual-device arms (subprocess, same pattern as
# tests/test_ingest.py)
# --------------------------------------------------------------------- #
SCRIPT_8DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    sys.path.insert(0, {tests!r})
    from test_ingest_sharded import run_sharded_vs_single
    from repro.ingest import ingest_cache_size, ingest_ladder_bound
    from repro.launch.mesh import make_stream_mesh

    mesh = make_stream_mesh()
    assert mesh.devices.size == 8, mesh
    c0 = ingest_cache_size()
    ing_s, ing_1, total, max_batch = run_sharded_vs_single(
        mesh, n_batches=50, seed=123)
    # per-device residency: each device holds exactly 1/8 of the ladder
    assert ing_s.store.device_bytes() * 8 == ing_1.store.device_bytes(), (
        ing_s.store.device_bytes(), ing_1.store.device_bytes())
    # compile discipline: both arms together stay under the a-priori
    # sharded + single ladder bound
    bound = (ingest_ladder_bound(total, max_batch, sharded=True)
             + ingest_ladder_bound(total, max_batch))
    assert ingest_cache_size() - c0 <= bound, (ingest_cache_size() - c0,
                                               bound)
    print("OK sharded-8dev", total, "rows")
""")


def test_sharded_store_bit_identical_8dev_50_batches():
    """Acceptance: 50 mixed insert/delete batches on a forced 8-virtual-
    device mesh — graphs and displaced-row sets bit-identical to the
    single-device store, per-device bytes exactly 1/8, jit cache within
    the sharded ladder bound."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_8DEV.format(src=SRC, tests=TESTS)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK sharded-8dev" in out.stdout


# The elastic arm streams a labeled mixture through device-ingest
# engines: sharded(8dev) -> checkpoint -> mesh-LESS restore -> continue,
# and mesh-less -> checkpoint -> 8-dev sharded restore -> continue; both
# survivors must finish bit-identical to an uninterrupted oracle.
ELASTIC = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core.stream import StreamEngine
    from repro.data.synth import StreamSpec, gaussian_mixture_stream
    from repro.graph.dynamic import DynamicGraph
    from repro.launch.mesh import make_stream_mesh

    spec = StreamSpec(total_vertices=320, batch_size=40, seed=9, emb_dim=4,
                      class_sep=6.0, noise=0.9, frac_deleted=0.12,
                      frac_unlabeled=0.85, frac_labeled=0.03)
    batches = [b for b, _ in gaussian_mixture_stream(spec)]
    mesh = make_stream_mesh()
    assert mesh.devices.size == 8

    g_ref = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    ref = StreamEngine(g_ref, delta=1e-4, ingest="device")
    for b in batches:
        ref.step(b)

    # sharded(8dev) -> checkpoint -> mesh-less restore -> continue
    ga = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    ea = StreamEngine(ga, delta=1e-4, ingest="device", mesh=mesh)
    assert ea.ingestor.store.n_shards == 8
    for b in batches[:4]:
        ea.step(b)
    ea.checkpoint({dir_a!r})
    ra = StreamEngine.restore({dir_a!r})
    assert ra.ingestor.store.n_shards == 1
    for b in batches[4:]:
        ra.step(b)
    for name in ("f", "labels", "alive", "knn_idx", "knn_wgt"):
        assert np.array_equal(getattr(ra.graph, name),
                              getattr(g_ref, name)), "a:" + name

    # mesh-less -> checkpoint -> sharded(8dev) restore -> continue
    gb = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eb = StreamEngine(gb, delta=1e-4, ingest="device")
    for b in batches[:4]:
        eb.step(b)
    eb.checkpoint({dir_b!r})
    rb = StreamEngine.restore({dir_b!r}, mesh=make_stream_mesh())
    assert rb.ingestor.store.n_shards == 8
    store, orig = rb.ingestor.store, eb.ingestor.store
    assert store.count == orig.count and store.capacity == orig.capacity
    np.testing.assert_array_equal(np.asarray(store.valid),
                                  np.asarray(orig.valid))
    np.testing.assert_array_equal(np.asarray(store.kth),
                                  np.asarray(orig.kth))
    for b in batches[4:]:
        rb.step(b)
    for name in ("f", "labels", "alive", "knn_idx", "knn_wgt"):
        assert np.array_equal(getattr(rb.graph, name),
                              getattr(g_ref, name)), "b:" + name
    print("OK elastic-sharded", ra.commits, rb.commits)
""")


def test_elastic_checkpoint_sharded_both_directions_8dev(tmp_path):
    """Acceptance: checkpoints save the store mesh-independent — a
    sharded(8-dev) engine restores onto 1 device and a 1-device engine
    restores onto the 8-device mesh, both continue streaming to labels
    bit-identical with the uninterrupted oracle."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_STREAM_TRANSPORT", None)
    out = subprocess.run(
        [sys.executable, "-c", ELASTIC.format(
            src=SRC, dir_a=str(tmp_path / "a"), dir_b=str(tmp_path / "b"))],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK elastic-sharded" in out.stdout
