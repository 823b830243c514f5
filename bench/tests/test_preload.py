"""The bulk preload equals a from-scratch build and streams like one."""

import numpy as np
import pytest

import cells
import preload


@pytest.fixture(scope="module")
def built():
    cfg = {**cells.cell("arxiv.churn-read")["config"], "rows": 2048, "labelled": 1100}
    data = preload.make_data(cfg, 12345678901, 2048)
    return cfg, data, preload.state_arrays(cfg, data)


def test_lists_and_edges_match_build_knn_graph(built):
    from repro.graph.knn import build_knn_graph, knn_edges
    from repro.graph.structures import coo_to_csr

    cfg, data, st = built
    n, k = data.n0, cfg["k"]
    src, dst, wgt = knn_edges(data.emb[:n], k=k)
    assert np.array_equal(st["knn_idx"], dst.reshape(n, k))
    assert st["knn_wgt"].tobytes() == wgt.reshape(n, k).tobytes()
    want = build_knn_graph(data.emb[:n], k=k)
    got = coo_to_csr(n, st["src"], st["dst"], st["wgt"])
    for a in ("rowptr", "col", "wgt"):
        assert getattr(got, a).tobytes() == getattr(want, a).tobytes()


def _serve(g, windows):
    from repro.core.stream import StreamEngine
    from repro.serving.lp_service import LPService

    svc = LPService(StreamEngine(g, ingest="device"), window_ops=512, max_pending_ops=1024)
    with svc:
        for w in windows:
            svc.mutate(**w)
            svc.sync()
    return g


def test_preloaded_engine_streams_like_a_streamed_one(built):
    from repro.graph.dynamic import DynamicGraph

    cfg, data, st = built
    n, d, k = data.n0, cfg["emb_dim"], cfg["k"]
    rng = np.random.default_rng(0)
    mixed = []
    for i in range(3):
        lo = n + 200 * i
        rel = rng.choice(n, 40, replace=False)
        mixed.append({"ins_emb": data.emb[lo:lo + 200],
                      "ins_labels": np.full(200, -1, np.int8),
                      "del_ids": np.arange(30 * i, 30 * i + 30),
                      "rel_ids": rel, "rel_labels": data.cls[rel]})
    a = DynamicGraph(emb_dim=d, k=k)
    a.load_state_arrays(st)
    _serve(a, mixed)
    b = DynamicGraph(emb_dim=d, k=k)
    load = [{"ins_emb": data.emb[lo:lo + 512], "ins_labels": data.labels0[lo:lo + 512]}
            for lo in range(0, n, 512)]
    _serve(b, load + mixed)
    for name in ("knn_idx", "knn_wgt", "src", "dst", "wgt", "labels", "alive"):
        assert getattr(a, name).tobytes() == getattr(b, name).tobytes(), name
    # both sets of labels sit at the same fixed point to within a few delta
    assert np.abs(a.f - b.f).max() < 2e-3
