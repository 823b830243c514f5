"""Device argkmin kernel: XLA twin vs Pallas (interpret) agreement, and
candidate coverage of the host oracle's canonical top-k.

The bit-equality contract (``graph.knn`` module docstring) only needs
the kernel to return candidate *supersets* covering the canonical top-k
plus an exact displacement mask — canonical re-selection happens on the
host.  These tests pin both properties, including the tie/duplicate and
dead-row corners.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.knn import SELECT_MARGIN, normalize_rows, pair_weights, \
    selection_slack, topk_pairs
from repro.kernels.argkmin import argkmin_candidates


def _make(rng, c, d, m, k, dead_frac=0.1, dup=False):
    """Store of ``c`` rows whose last ``m`` are the arriving batch."""
    emb = rng.normal(size=(c, d)).astype(np.float32)
    if dup:  # mass duplicates force deep ties
        emb[: c // 2] = emb[0]
    embn = normalize_rows(emb)
    base_id = c - m
    valid = np.ones(c, bool)
    n_dead = int(dead_frac * base_id)
    if n_dead:
        valid[rng.choice(base_id, n_dead, replace=False)] = False
    # plausible existing k-th weights for the old rows; -inf = under-full
    kth = np.full(c, -np.inf, np.float32)
    kth[: base_id] = rng.uniform(0.4, 0.9, base_id).astype(np.float32)
    kth[rng.choice(c, max(1, c // 8), replace=False)] = -np.inf
    batch = embn[base_id:]
    bvalid = np.ones(m, bool)
    return embn, valid, kth, batch, bvalid, base_id


def _run(backend, embn, valid, kth, batch, bvalid, base_id, d, k, br=128):
    return argkmin_candidates(
        jnp.asarray(embn), jnp.asarray(valid), jnp.asarray(kth),
        jnp.asarray(batch), jnp.asarray(bvalid), base_id,
        selection_slack(d), k=k, backend=backend, block_rows=br,
        interpret=True)


def _canonical_topk(embn, valid, batch, base_id, k):
    """Host oracle: each batch row's canonical top-k over the valid store
    (``pair_weights`` total order, self excluded)."""
    c, m = len(embn), len(batch)
    w = pair_weights(batch[:, None, :], embn[None, :, :])
    ids = np.broadcast_to(np.arange(c, dtype=np.int64), w.shape).copy()
    w = w.copy()
    w[:, ~valid] = -np.inf
    w[np.arange(m), base_id + np.arange(m)] = -np.inf
    return topk_pairs(w, ids, k)[0]


@pytest.mark.parametrize("dup", [False, True])
@pytest.mark.parametrize("c,d,m,k", [(256, 16, 8, 5), (512, 33, 16, 3)])
def test_xla_vs_pallas_interpret_agree(c, d, m, k, dup):
    """The two paths contract in opposite orientations, so their fast
    similarities may differ in the last bit.  What both must meet is the
    contract canonical re-selection relies on: candidates that cover the
    canonical top-k, fast values within rounding of the exact ones, and
    a displacement mask that follows the slack rule exactly wherever the
    rule is decided by more than rounding."""
    rng = np.random.default_rng(c + d + dup)
    embn, valid, kth, batch, bvalid, base_id = _make(rng, c, d, m, k, dup=dup)
    want_i = _canonical_topk(embn, valid, batch, base_id, k)
    w64 = (batch.astype(np.float64) @ embn.T.astype(np.float64) + 1.0) * 0.5
    thr = kth.astype(np.float64) - selection_slack(d)
    colmax = w64.max(axis=0)
    rule = valid & (np.arange(c) < base_id) & (colmax > thr)
    decided = np.abs(colmax - thr) > 1e-6
    for backend in ("xla", "pallas"):
        val, idx, disp = (np.asarray(a) for a in _run(
            backend, embn, valid, kth, batch, bvalid, base_id, d, k))
        fin = np.isfinite(val)
        for q in range(m):
            need = set(want_i[q][want_i[q] >= 0])
            assert need <= set(idx[q][fin[q]]), (backend, q)
        exact = w64[np.arange(m)[:, None], idx]
        assert np.abs(val - exact)[fin].max() <= 1e-6, backend
        np.testing.assert_array_equal(disp[decided], rule[decided],
                                      err_msg=backend)


def test_no_self_no_dead_candidates():
    rng = np.random.default_rng(3)
    c, d, m, k = 256, 12, 16, 4
    embn, valid, kth, batch, bvalid, base_id = _make(rng, c, d, m, k,
                                                     dead_frac=0.3)
    for backend in ("xla", "pallas"):
        val, idx, disp = (np.asarray(a) for a in _run(
            backend, embn, valid, kth, batch, bvalid, base_id, d, k))
        fin = np.isfinite(val)
        rows, cols = np.nonzero(fin)
        cand = idx[rows, cols]
        assert not (cand == (base_id + rows)).any(), backend  # no self
        assert valid[cand].all(), backend  # no dead rows
        assert not disp[~valid].any() and not disp[base_id:].any(), backend


def test_candidates_cover_canonical_topk():
    """Every canonical top-k neighbor (host ``pair_weights`` total order)
    appears in the kernel's candidate superset."""
    rng = np.random.default_rng(11)
    c, d, m, k = 384, 24, 24, 5
    embn, valid, kth, batch, bvalid, base_id = _make(rng, c, d, m, k)
    want_i = _canonical_topk(embn, valid, batch, base_id, k)
    for backend in ("xla", "pallas"):
        val, idx, _ = (np.asarray(a) for a in _run(
            backend, embn, valid, kth, batch, bvalid, base_id, d, k,
            br=128))
        for q in range(m):
            cand = set(idx[q][np.isfinite(val[q])])
            need = set(want_i[q][want_i[q] >= 0])
            assert need <= cand, (backend, q, need - cand)


def test_displacement_mask_matches_slack_rule():
    """disp == alive old rows whose kth the batch beats within slack,
    computed straight from the definition."""
    rng = np.random.default_rng(5)
    c, d, m, k = 256, 10, 8, 4
    embn, valid, kth, batch, bvalid, base_id = _make(rng, c, d, m, k)
    w = pair_weights(batch[:, None, :], embn[None, :, :]).astype(np.float64)
    # the kernel computes (dot + 1)/2 in f32; recompute the same way
    s = batch.astype(np.float32) @ embn.T.astype(np.float32)
    w32 = (s + np.float32(1.0)) * np.float32(0.5)
    w32[np.arange(m), base_id + np.arange(m)] = np.nan  # self is still a col
    colmax = np.nanmax(w32, axis=0)
    slack = np.float32(selection_slack(d))
    want = valid & (np.arange(c) < base_id) & (colmax > kth - slack)
    for backend in ("xla", "pallas"):
        _, _, disp = _run(backend, embn, valid, kth, batch, bvalid,
                          base_id, d, k)
        np.testing.assert_array_equal(np.asarray(disp), want)
    del w  # (canonical weights unused: disp is defined on the fast path)


def test_underfull_store_pads_with_minus_inf():
    """A store smaller than k+margin returns what exists; empty slots are
    -inf and every real candidate is kept."""
    rng = np.random.default_rng(9)
    d, k = 8, 5
    embn = normalize_rows(rng.normal(size=(16, d)).astype(np.float32))
    valid = np.ones(16, bool)
    kth = np.full(16, -np.inf, np.float32)
    base_id, m = 12, 4
    for backend in ("xla", "pallas"):
        val, idx, disp = (np.asarray(a) for a in _run(
            backend, embn, valid, kth, embn[12:], np.ones(4, bool),
            base_id, d, k, br=16))
        assert val.shape[1] == min(k + SELECT_MARGIN, 16)
        assert np.isfinite(val).all()  # 15 non-self rows > topk width
        assert disp[:12].all()  # -inf kth: everything is displaced
