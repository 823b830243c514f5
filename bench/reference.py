"""Plain reference of the served semantics, independent of the program.

``RefGraph`` replays the admitted windows on its own copy of the preload:

* deletions kill their rows and drop every list entry that points at
  them; a list with a hole keeps its remaining entries in canonical
  order, and the hole refills only as later arrivals merge in;
* an insertion's list is the canonical top-k over every alive row
  (earlier rows and the rest of its window, never itself), and every
  earlier alive row whose canonical top-k over its list plus the window
  differs takes that merged list;
* relabels set the ground-truth label of alive rows, last write wins.

Candidates are nominated by a blocked ``jnp`` pass (top ``k + margin``
per arriving row; per earlier row, the window rows that can beat its
k-th weight) and re-selected canonically on the host (``lp``).  With
``dtype=bfloat16`` the same replay is the control: similarities and
weights pass through bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import lp
from preload import NOMINATE_CHUNK, NOMINATE_GROUP, top_t

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST, "default": jax.lax.Precision.DEFAULT}

ROW_FLOOR = 8


def _bucket(n: int) -> int:
    b = ROW_FLOOR
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("t", "ch", "precision"))
def _window_pass(store, valid, batch, bvalid, base, *, t, ch, precision):
    """For window rows: top-t (cos, id) over valid store rows other than
    themselves; for every store row: the best cos over the window."""
    m = batch.shape[0]
    qids = base + jnp.arange(m, dtype=jnp.int32)

    def body(c, carry):
        rv, ri, colmax = carry
        blk = jax.lax.dynamic_slice_in_dim(store, c * ch, ch)
        ok = jax.lax.dynamic_slice_in_dim(valid, c * ch, ch)
        ids = c * ch + jnp.arange(ch, dtype=jnp.int32)
        s = jnp.dot(batch, blk.T, precision=precision,
                    preferred_element_type=jnp.float32).astype(store.dtype).astype(jnp.float32)
        cm = jnp.max(jnp.where(bvalid[:, None], s, -jnp.inf), axis=0)
        colmax = jax.lax.dynamic_update_slice_in_dim(colmax, cm, c * ch, 0)
        s = jnp.where(ok[None, :] & (ids[None, :] != qids[:, None]), s, -jnp.inf)
        v, p = top_t(s, t, NOMINATE_GROUP)
        mv, mp = jax.lax.top_k(jnp.concatenate([rv, v], 1), t)
        idx = jnp.take_along_axis(jnp.concatenate([ri, c * ch + p], 1), mp, 1)
        return mv, idx, colmax

    init = (jnp.full((m, t), -jnp.inf, jnp.float32), jnp.full((m, t), -1, jnp.int32),
            jnp.full((store.shape[0],), -jnp.inf, jnp.float32))
    return jax.lax.fori_loop(0, store.shape[0] // ch, body, init)


@functools.partial(jax.jit, static_argnames=("t", "precision"))
def _rows_vs_window(rows, batch, bvalid, *, t, precision):
    s = jnp.dot(rows, batch.T, precision=precision,
                preferred_element_type=jnp.float32).astype(rows.dtype).astype(jnp.float32)
    s = jnp.where(bvalid[None, :], s, -jnp.inf)
    return jax.lax.top_k(s, t)


@jax.jit
def _put_rows(store, valid, rows, rvalid, at):
    store = jax.lax.dynamic_update_slice_in_dim(store, rows, at, 0)
    return store, jax.lax.dynamic_update_slice_in_dim(valid, rvalid, at, 0)


@jax.jit
def _kill(valid, ids):
    return valid.at[ids].set(False, mode="drop")


class RefGraph:
    """The reference's own copy of the graph, advanced one window at a time."""

    def __init__(self, state: dict, k: int, capacity: int, dtype=jnp.float32,
                 precision: str = "highest"):
        self.k = k
        self.dtype = dtype
        self.precision = PRECISIONS[precision]
        n, d = state["embn"].shape
        self.dim = d
        self.n = n
        self.ch = min(NOMINATE_CHUNK, max(2048, _bucket(capacity)))
        # room for one padded window past the last row
        cap = -self.ch * (-(capacity + _bucket(capacity - n + 1)) // self.ch)
        self._embn = np.zeros((cap, d), np.float32)
        self._labels = np.full(cap, lp.UNLABELLED, np.int8)
        self._alive = np.zeros(cap, bool)
        self._ki = np.full((cap, k), -1, np.int64)
        self._kw = np.full((cap, k), -np.inf, np.float32)
        self._embn[:n] = state["embn"]
        self._labels[:n] = state["labels"]
        self._alive[:n] = state["alive"]
        self._ki[:n] = state["knn_idx"]
        self._kw[:n] = self._round(np.asarray(state["knn_wgt"], np.float32))
        host = np.zeros((cap, lp.dim_pad(d)), np.float32)
        host[:n, :d] = self.embn
        self.store = jnp.asarray(host, dtype)
        self.valid = jnp.asarray(self._alive)

    embn = property(lambda self: self._embn[: self.n])
    labels = property(lambda self: self._labels[: self.n])
    alive = property(lambda self: self._alive[: self.n])
    knn_idx = property(lambda self: self._ki[: self.n])
    knn_wgt = property(lambda self: self._kw[: self.n])

    @property
    def num_nodes(self) -> int:
        return self.n

    def _round(self, w: np.ndarray) -> np.ndarray:
        if self.dtype == jnp.float32:
            return w
        return np.array(jnp.asarray(w, self.dtype).astype(jnp.float32))

    def _weights(self, qn, bn) -> np.ndarray:
        return self._round(lp.pair_weights(qn, bn))

    def _pad(self, x: np.ndarray, rows: int) -> jax.Array:
        out = np.zeros((rows, self.store.shape[1]), np.float32)
        out[: len(x), : self.dim] = x
        return jnp.asarray(out, self.dtype)

    def apply(self, del_ids, ins_emb, ins_labels, rel_ids, rel_labels) -> None:
        k = self.k
        dels = np.unique(np.asarray(del_ids, np.int64))
        dels = dels[(dels >= 0) & (dels < self.num_nodes)]
        dels = dels[self.alive[dels]]
        if len(dels):
            self.alive[dels] = False
            self.knn_idx[dels] = -1
            self.knn_wgt[dels] = -np.inf
            gone = np.zeros(self.num_nodes, bool)
            gone[dels] = True
            hit = (self.knn_idx >= 0) & gone[np.maximum(self.knn_idx, 0)]
            rows = np.flatnonzero(hit.any(axis=1))
            w, i = self.knn_wgt[rows], self.knn_idx[rows]
            w[hit[rows]] = -np.inf
            i[hit[rows]] = -1
            self.knn_idx[rows], self.knn_wgt[rows] = lp.topk_pairs(w, i, k)
            self.valid = _kill(self.valid, jnp.asarray(
                np.pad(dels, (0, _bucket(len(dels)) - len(dels)),
                       constant_values=self.store.shape[0]).astype(np.int32)))
        m = len(ins_emb)
        if m:
            self._insert(np.asarray(ins_emb, np.float32), np.asarray(ins_labels, np.int8))
        rel = np.asarray(rel_ids, np.int64)
        if len(rel):
            lab = np.asarray(rel_labels, np.int8)
            ok = (rel >= 0) & (rel < self.num_nodes)
            ok[ok] = self.alive[rel[ok]]
            self.labels[rel[ok]] = lab[ok]

    def _insert(self, emb: np.ndarray, labels: np.ndarray) -> None:
        k, m, base = self.k, len(emb), self.num_nodes
        t = k + lp.SELECT_MARGIN
        new_ids = np.arange(base, base + m, dtype=np.int64)
        embn_new = lp.normalize_rows(emb)
        mp = _bucket(m)
        if base + mp > len(self._alive):
            raise RuntimeError("reference capacity exceeded")
        kth_old = self.knn_wgt[:, k - 1].copy()
        self.n = base + m
        self._embn[base:base + m] = embn_new
        self._labels[base:base + m] = labels
        self._alive[base:base + m] = True
        batch = self._pad(embn_new, mp)
        bvalid = jnp.asarray(np.arange(mp) < m)
        self.store, self.valid = _put_rows(self.store, self.valid, batch, bvalid,
                                           jnp.int32(base))
        val, idx, colmax = _window_pass(self.store, self.valid, batch, bvalid, jnp.int32(base),
                                        t=t, ch=self.ch, precision=self.precision)
        val = np.asarray(val)[:m]
        cand = np.where(np.isfinite(val), np.asarray(idx)[:m].astype(np.int64), -1)
        # canonical lists of the arriving rows
        cw = np.full(cand.shape, -np.inf, np.float32)
        r, j = np.nonzero(cand >= 0)
        if len(r):
            cw[r, j] = self._weights(embn_new[r], self.embn[cand[r, j]])
        self.knn_idx[new_ids], self.knn_wgt[new_ids] = lp.topk_pairs(cw, cand, k)
        # earlier rows that a window row can displace
        colw = (np.asarray(colmax)[:base] + 1.0) * 0.5
        old = np.flatnonzero(self.alive[:base]
                             & (colw > kth_old - lp.selection_slack(self.dim)))
        for lo in range(0, len(old), 8192):
            rows = old[lo:lo + 8192]
            fp = _bucket(len(rows))
            _, top = _rows_vs_window(self._pad(self.embn[rows], fp), batch, bvalid,
                                     t=min(t, mp), precision=self.precision)
            top = np.asarray(top)[: len(rows)].astype(np.int64)
            top = np.where(top < m, top, -1)
            qr, qc = np.nonzero(top >= 0)
            bw = np.full(top.shape, -np.inf, np.float32)
            bw[qr, qc] = self._weights(self.embn[rows[qr]], embn_new[top[qr, qc]])
            mw = np.concatenate([self.knn_wgt[rows], bw], axis=1)
            mi = np.concatenate([self.knn_idx[rows], np.where(top >= 0, base + top, -1)], axis=1)
            self.knn_idx[rows], self.knn_wgt[rows] = lp.topk_pairs(mw, mi, k)

    def edges(self):
        return lp.edges_from_lists(self.knn_idx, self.knn_wgt)

    def problem(self) -> lp.Problem:
        src, dst, wgt = self.edges()
        return lp.build_problem(src, dst, wgt, self.labels, self.alive, 4 * self.k)


def answers(labels, alive, f, ids, cutoff=0.5):
    """(pred, conf) of a read at one commit, from that commit's state."""
    ids = np.asarray(ids, np.int64)
    n = len(labels)
    pred = np.full(len(ids), lp.UNLABELLED, np.int8)
    conf = np.zeros(len(ids), np.float32)
    known = (ids >= 0) & (ids < n)
    known[known] = alive[ids[known]]
    kn = ids[known]
    lab = labels[kn]
    fv = np.asarray(f, np.float32)[kn]
    seeded = lab != lp.UNLABELLED
    pred[known] = np.where(seeded, lab, (fv >= np.float32(cutoff)).astype(np.int8))
    conf[known] = np.where(seeded, np.float32(1.0),
                           np.maximum(fv, np.float32(1.0) - fv))
    return pred, conf
