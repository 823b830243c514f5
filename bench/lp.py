"""Plain label-propagation semantics, written independently of the program.

The benchmark's preload and its reference both build on these functions,
and neither imports the system under test.  They restate the rules the
system documents for its state:

* kNN lists: cosine similarity of row-normalized float32 embeddings,
  weight ``(cos + 1) / 2`` summed in one fixed order over the feature
  axis, top-k under the total order (weight descending, id ascending);
* edges: the unique undirected pairs ``{a, b}`` with ``b`` in ``a``'s list
  or ``a`` in ``b``'s, both directions stored, in (src, dst) order;
* the propagation problem over alive unlabelled rows: labelled neighbours
  fold into per-row class sums, each row keeps at most ``max_k`` of its
  heaviest unlabelled neighbours (ties keep the lower id), and the
  committed labels are a fixed point of the weighted neighbourhood average
  ``f_u = (sum_v w_uv f_v + wl1_u) / (sum_v w_uv + wl0_u + wl1_u)``.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import numpy as np

UNLABELLED = -1
SELECT_MARGIN = 8  # candidates nominated beyond k before canonical re-selection


def selection_slack(dim: int) -> float:
    """Similarity tolerance that keeps float32 rounding out of pruning tests."""
    return 1e-5 + 1e-7 * dim


def dim_pad(d: int) -> int:
    """Feature axis padded to a multiple of 8 (zeros are inert in dots)."""
    return max(8, -8 * (-d // 8))


def normalize_rows(x: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(x, axis=1, keepdims=True)
    return x / np.maximum(n, 1e-12)


def pair_weights(qn: np.ndarray, bn: np.ndarray) -> np.ndarray:
    """Canonical edge weight of row-aligned (query, base) pairs, (P, D) each."""
    prod = np.multiply(qn, bn, dtype=np.float32)
    cos = prod.sum(axis=-1, dtype=np.float32)
    return ((cos + np.float32(1.0)) * np.float32(0.5)).astype(np.float32, copy=False)


def topk_pairs(wgt: np.ndarray, idx: np.ndarray, k: int):
    """Per-row top-k by (weight desc, id asc); -inf slots come back (-1, -inf)."""
    r, c = wgt.shape
    kc = min(k, c)
    order = np.lexsort((idx, -wgt), axis=-1)[:, :kc]
    rows = np.arange(r)[:, None]
    top_w = wgt[rows, order]
    top_i = np.where(np.isfinite(top_w), idx[rows, order], -1)
    if kc < k:
        top_i = np.concatenate([top_i, np.full((r, k - kc), -1, top_i.dtype)], axis=1)
        top_w = np.concatenate([top_w, np.full((r, k - kc), -np.inf, np.float32)], axis=1)
    return top_i.astype(np.int64), top_w.astype(np.float32)


def canonical_topk(qn: np.ndarray, q_ids: np.ndarray, embn: np.ndarray,
                   cand: np.ndarray, k: int, chunk: int = 16384, threads: int = 8):
    """Canonical top-k of each query row over its candidate ids.

    ``cand`` (Q, T) int64 holds global ids into ``embn`` (-1 = empty); a
    candidate equal to the query's own id never counts.  Chunks of rows
    run on a few threads (NumPy releases the interpreter lock)."""
    out_i = np.empty((len(qn), k), np.int64)
    out_w = np.empty((len(qn), k), np.float32)

    def one(lo):
        c = cand[lo:lo + chunk]
        ok = (c >= 0) & (c != q_ids[lo:lo + chunk, None])
        cw = pair_weights(qn[lo:lo + chunk, None, :], embn[np.maximum(c, 0)])
        cw = np.where(ok, cw, np.float32(-np.inf))
        out_i[lo:lo + chunk], out_w[lo:lo + chunk] = topk_pairs(cw, c, k)

    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        for f in [ex.submit(one, lo) for lo in range(0, len(qn), chunk)]:
            f.result()
    return out_i, out_w


def edges_from_lists(knn_idx: np.ndarray, knn_wgt: np.ndarray):
    """Undirected edge arrays (both directions, (src, dst) ascending)."""
    s, col = np.nonzero(knn_idx >= 0)
    s = s.astype(np.int64)
    d = knn_idx[s, col]
    w = knn_wgt[s, col]
    recip = (knn_idx[d] == s[:, None]).any(axis=1)
    keep = ~recip | (s < d)
    s, d, w = s[keep], d[keep], w[keep]
    src = np.concatenate([s, d])
    dst = np.concatenate([d, s])
    wgt = np.concatenate([w, w]).astype(np.float32)
    order = np.argsort(src << np.int64(32) | dst, kind="stable")
    return src[order], dst[order], wgt[order]


@dataclasses.dataclass
class Problem:
    """ELL form of the propagation problem over alive unlabelled rows."""

    unl_ids: np.ndarray  # (U,) global ids
    nbr: np.ndarray  # (U, K) int32 row indices into unl_ids, -1 empty
    wgt: np.ndarray  # (U, K) float32
    wl0: np.ndarray  # (U,) float32
    wl1: np.ndarray  # (U,) float32


def build_problem(src, dst, wgt, labels, alive, max_k: int) -> Problem:
    live = alive[src] & alive[dst]
    src, dst, wgt = src[live], dst[live], wgt[live]
    unl = alive & (labels == UNLABELLED)
    unl_ids = np.flatnonzero(unl)
    u = len(unl_ids)
    remap = np.full(len(labels), -1, np.int64)
    remap[unl_ids] = np.arange(u)
    s_unl = unl[src]
    uu = s_unl & unl[dst]
    rows, cols, w = remap[src[uu]], remap[dst[uu]], wgt[uu]
    # edges arrive in (src, dst) order; a stable sort by (row, weight
    # descending) keeps the lower id first among equal weights (weights
    # are positive, so their bits order like the values)
    wkey = np.int64(0x7FFFFFFF) - w.view(np.int32).astype(np.int64)
    order = np.argsort(rows << np.int64(32) | wkey, kind="stable")
    rows, cols, w = rows[order], cols[order], w[order]
    deg = np.bincount(rows, minlength=u)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(len(rows)) - np.repeat(start, deg)
    keep = slot < max_k
    rows, cols, w, slot = rows[keep], cols[keep], w[keep], slot[keep]
    kk = max(1, int(slot.max()) + 1 if len(slot) else 1)
    nbr = np.full((u, kk), -1, np.int32)
    wg = np.zeros((u, kk), np.float32)
    nbr[rows, slot] = cols
    wg[rows, slot] = w
    ul = s_unl & ~unl[dst]
    lab = labels[dst[ul]]
    r = remap[src[ul]]
    wl0 = np.bincount(r[lab == 0], weights=wgt[ul][lab == 0], minlength=u)
    wl1 = np.bincount(r[lab == 1], weights=wgt[ul][lab == 1], minlength=u)
    return Problem(unl_ids=unl_ids, nbr=nbr, wgt=wg,
                   wl0=wl0.astype(np.float32), wl1=wl1.astype(np.float32))


def residuals(p: Problem, f_unl: np.ndarray) -> np.ndarray:
    """|T(F)_u - F_u| per unlabelled row, in float64 (0 for isolated rows)."""
    f = np.asarray(f_unl, np.float64)
    w = p.wgt.astype(np.float64)
    fv = np.where(p.nbr >= 0, f[np.maximum(p.nbr, 0)], 0.0)
    wall = w.sum(axis=1) + p.wl0 + p.wl1
    num = (w * fv).sum(axis=1) + p.wl1
    r = np.abs(num - f * wall) / np.maximum(wall, 1e-300)
    return np.where(wall > 0, r, 0.0)
