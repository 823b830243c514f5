"""Property-based stream equivalence: for ANY random mixed insert/delete
stream, ``StreamEngine`` labels are bit-identical to a full per-batch
``DynLP`` recompute, on both the ``ref`` and ``ell_pallas`` backends.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.dynlp import DynLP
from repro.core.stream import StreamEngine
from repro.graph.dynamic import UNLABELED, BatchUpdate, DynamicGraph
from repro.launch.mesh import make_stream_mesh

EMB_DIM = 8


def _random_batches(seed, n_batches, batch_size, frac_del, hostile_dels,
                    include_empty):
    """Random two-Gaussian insert/delete stream.  ``hostile_dels`` mixes
    duplicate and out-of-range ids into the delete sets (both engines
    must shrug them off identically); ``include_empty`` splices in an
    all-empty Δ_t."""
    rng = np.random.default_rng(seed)
    batches = []
    next_id = 0
    for b in range(n_batches):
        n = batch_size
        cls = rng.integers(0, 2, n).astype(np.int8)
        emb = np.zeros((n, EMB_DIM), np.float32)
        emb[:, 0] = np.where(cls == 1, 3.0, -3.0)
        emb += rng.normal(0, 0.9, (n, EMB_DIM)).astype(np.float32)
        labels = np.full(n, UNLABELED, np.int8)
        if b == 0:  # seed both classes so propagation has sources
            labels[0] = cls[0]
            labels[1] = 1 - cls[0]
            cls[1] = 1 - cls[0]
            emb[1, 0] = -emb[0, 0]
        n_del = int(round(frac_del * n)) if next_id else 0
        del_ids = rng.integers(0, next_id, n_del).astype(np.int64) \
            if n_del else np.zeros(0, np.int64)
        if hostile_dels and next_id:
            del_ids = np.concatenate([
                del_ids, del_ids[:2],  # duplicates
                np.array([next_id + 17, -1], np.int64),  # never-seen ids
            ])
        batches.append(BatchUpdate(ins_emb=emb, ins_labels=labels,
                                   del_ids=del_ids))
        next_id += n
    if include_empty:
        batches.insert(n_batches // 2 + 1, BatchUpdate(
            ins_emb=np.zeros((0, EMB_DIM), np.float32),
            ins_labels=np.zeros(0, np.int8),
            del_ids=np.zeros(0, np.int64)))
    return batches


@given(st.integers(0, 10_000), st.integers(2, 4), st.integers(10, 30),
       st.floats(0.0, 0.3), st.booleans(), st.booleans(),
       st.sampled_from(["ref", "ell_pallas"]))
@settings(max_examples=8, deadline=None)
def test_stream_bit_identical_to_dynlp_recompute(
        seed, n_batches, batch_size, frac_del, hostile_dels, include_empty,
        backend):
    """After every Δ_t the streamed labels equal the full DynLP recompute
    bit for bit — same iteration count, same convergence, same f."""
    batches = _random_batches(seed, n_batches, batch_size, frac_del,
                              hostile_dels, include_empty)
    g_s = DynamicGraph(emb_dim=EMB_DIM, k=4)
    g_d = DynamicGraph(emb_dim=EMB_DIM, k=4)
    eng = StreamEngine(g_s, delta=1e-4, backend=backend, block_rows=64)
    dyn = DynLP(g_d, delta=1e-4, backend=backend)
    for i, batch in enumerate(batches):
        st_s = eng.step(batch)
        st_d = dyn.step(batch)
        assert st_s.iterations == st_d.iterations, f"batch {i}"
        assert st_s.converged == st_d.converged, f"batch {i}"
        assert st_s.num_unlabeled == st_d.num_unlabeled, f"batch {i}"
        np.testing.assert_array_equal(g_s.f, g_d.f,
                                      err_msg=f"batch {i} ({backend})")
        np.testing.assert_array_equal(g_s.alive, g_d.alive)
    ids_s, pred_s = eng.predictions()
    ids_d, pred_d = dyn.predictions()
    np.testing.assert_array_equal(ids_s, ids_d)
    np.testing.assert_array_equal(pred_s, pred_d)


@given(st.integers(0, 10_000), st.integers(2, 3), st.integers(10, 24),
       st.floats(0.0, 0.25), st.booleans())
@settings(max_examples=6, deadline=None)
def test_pipelined_stream_bit_identical_to_dynlp(seed, n_batches,
                                                 batch_size, frac_del,
                                                 hostile_dels):
    """The overlapped submit/drain pipeline reaches the same fixpoint as
    the recompute too — staging t+1 while t is in flight never leaks."""
    batches = _random_batches(seed, n_batches, batch_size, frac_del,
                              hostile_dels, include_empty=False)
    g_p = DynamicGraph(emb_dim=EMB_DIM, k=4)
    g_d = DynamicGraph(emb_dim=EMB_DIM, k=4)
    eng = StreamEngine(g_p, delta=1e-4)
    dyn = DynLP(g_d, delta=1e-4)
    done = 0
    for batch in batches:
        if eng.submit(batch) is not None:
            done += 1
        dyn.step(batch)
    assert eng.drain() is not None
    done += 1
    assert done == len(batches) == eng.commits
    np.testing.assert_array_equal(g_p.f, g_d.f)


@given(st.integers(0, 10_000), st.integers(3, 5), st.integers(10, 24))
@settings(max_examples=6, deadline=None)
def test_pipelined_relabels_bit_identical_to_dynlp(seed, n_batches,
                                                   batch_size):
    """Relabels that land while the previous batch is still in flight
    (a seed promoted, demoted, or an unlabeled row reset to 0.5) keep the
    sequential order: the drained solve must not overwrite them."""
    batches = _random_batches(seed, n_batches, batch_size, 0.1,
                              hostile_dels=False, include_empty=False)
    rng = np.random.default_rng(seed + 1)
    seen = 0
    for b in batches:
        if seen:
            b.rel_ids = rng.choice(seen, min(seen, 6), replace=False)
            b.rel_labels = rng.choice(
                np.array([0, 1, UNLABELED], np.int8), len(b.rel_ids))
        seen += len(b.ins_emb)
    g_p = DynamicGraph(emb_dim=EMB_DIM, k=4)
    g_d = DynamicGraph(emb_dim=EMB_DIM, k=4)
    eng = StreamEngine(g_p, delta=1e-4)
    dyn = DynLP(g_d, delta=1e-4)
    for batch in batches:
        eng.submit(batch)
        dyn.step(batch)
    eng.drain()
    np.testing.assert_array_equal(g_p.labels, g_d.labels)
    np.testing.assert_array_equal(g_p.f, g_d.f)


@given(st.integers(0, 10_000), st.integers(2, 4), st.integers(10, 30),
       st.floats(0.0, 0.3), st.booleans(),
       st.sampled_from(["ref", "ell_pallas"]))
@settings(max_examples=6, deadline=None)
def test_transport_equivalence_halo_allgather_single(
        seed, n_batches, batch_size, frac_del, hostile_dels, backend):
    """For ANY random insert/delete stream, the sharded transports are
    bit-interchangeable: halo ≡ all-gather ≡ single-device, for both
    update bodies.  Random streams have no locality, so this also
    exercises saturated export budgets; correctness must never depend on
    which collective a batch happened to ride (overflow fallback
    included — the assertion holds whether or not any batch fell back)."""
    batches = _random_batches(seed, n_batches, batch_size, frac_del,
                              hostile_dels, include_empty=False)
    mesh = make_stream_mesh()  # 1 device in tier-1, 8 in the matrix job
    f_ref = None
    for transport in (None, "allgather", "halo"):
        g = DynamicGraph(emb_dim=EMB_DIM, k=4)
        eng = (StreamEngine(g, delta=1e-4, backend=backend, block_rows=64)
               if transport is None else
               StreamEngine(g, delta=1e-4, backend=backend, block_rows=64,
                            mesh=mesh, transport=transport))
        for batch in batches:
            eng.step(batch)
        if f_ref is None:
            f_ref = g.f.copy()
        else:
            np.testing.assert_array_equal(
                g.f, f_ref, err_msg=f"{transport} ({backend})")


@given(st.integers(0, 10_000), st.integers(8, 40))
@settings(max_examples=8, deadline=None)
def test_committed_view_is_frozen_copy(seed, batch_size):
    """The committed LabelView must be decoupled from the live graph: a
    later (un-drained) submit can't leak into it."""
    batches = _random_batches(seed, 2, batch_size, 0.1,
                              hostile_dels=False, include_empty=False)
    g = DynamicGraph(emb_dim=EMB_DIM, k=4)
    eng = StreamEngine(g, delta=1e-4)
    eng.step(batches[0])
    view = eng.committed_view()
    f_then = view.f.copy()
    eng.submit(batches[1])  # mutates g.f (supernode inits) pre-commit
    np.testing.assert_array_equal(view.f, f_then)
    assert not view.f.flags.writeable
    assert eng.committed_view() is view  # still batch 0's commit
    eng.drain()
    assert eng.committed_view() is not view
