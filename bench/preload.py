"""Seeded data and the bulk preload of a deployment's state.

Every row of a configuration is made from ``--seed`` in one pass and
loaded into the engine as a finished state, instead of through the write
path (which would cost minutes to hours at these sizes):

1. embeddings: a two-Gaussian mixture (class centres at +-class_sep/2 on
   axis 0, unit noise), drawn on the device in one jitted call;
2. exact kNN candidates nominated on the device by a plain blocked
   ``jnp`` pass (top ``k + SELECT_MARGIN`` per row, self excluded);
3. canonical re-selection on the host (``lp.canonical_topk``), so the
   lists equal a from-scratch build over the same rows bit for bit;
4. edges symmetrized in (src, dst) order;
5. ``f`` from a converged full Jacobi solve of the capped problem.

``state_arrays`` returns the arrays in the layout the engine restores
from (``DynamicGraph.load_state_arrays``).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import lp

NOMINATE_CHUNK = 32768  # store rows per matmul tile
NOMINATE_QUERY = 2048  # query rows per dispatch
NOMINATE_GROUP = 128  # lanes per group in the two-stage top-k


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """Independent 31-bit words from any whole-number seed."""
    return (np.random.SeedSequence(int(seed)).generate_state(n) & 0x7FFFFFFF).astype(np.int64)


@dataclasses.dataclass
class Data:
    """All rows a run can see: the preload and the pool of later inserts."""

    emb: np.ndarray  # (N0 + M, D) float32 raw embeddings
    cls: np.ndarray  # (N0 + M,) int8 generator class
    labels0: np.ndarray  # (N0,) int8 preload labels (UNLABELLED or class)
    n0: int


@functools.partial(jax.jit, static_argnames=("n", "d", "sep", "noise"))
def _mixture(key, *, n, d, sep, noise):
    kc, kn = jax.random.split(key)
    cls = jax.random.bernoulli(kc, 0.5, (n,)).astype(jnp.int8)
    centre = jnp.where(cls == 1, sep / 2, -sep / 2).astype(jnp.float32)
    emb = noise * jax.random.normal(kn, (n, d), jnp.float32)
    return emb.at[:, 0].add(centre), cls


def make_data(cfg: dict, seed: int, insert_rows: int) -> Data:
    n0, d = int(cfg["rows"]), int(cfg["emb_dim"])
    w = seed_words(seed)
    gen = cfg["data"]
    emb, cls = _mixture(jax.random.PRNGKey(int(w[0])), n=n0 + insert_rows, d=d,
                        sep=float(gen["class_sep"]), noise=float(gen["noise"]))
    emb, cls = np.asarray(emb), np.asarray(cls)
    labels0 = np.full(n0, lp.UNLABELLED, np.int8)
    lab = np.random.default_rng(int(w[1])).permutation(n0)[: int(cfg["labelled"])]
    labels0[lab] = cls[lab]
    return Data(emb=emb, cls=cls, labels0=labels0, n0=n0)


def top_t(s, t: int, g: int):
    """Exact top-t (value, column) per row of ``s`` (R, C), C a multiple of g.

    An element of a row's top t lies in one of the t groups of g columns
    with the largest maxima, so only those groups are searched."""
    r, c = s.shape
    s3 = s.reshape(r, c // g, g)
    _, gi = jax.lax.top_k(s3.max(axis=2), t)
    sel = jnp.take_along_axis(s3, gi[:, :, None], axis=1).reshape(r, t * g)
    v, p = jax.lax.top_k(sel, t)
    return v, jnp.take_along_axis(gi, p // g, axis=1) * g + p % g


@functools.partial(jax.jit, static_argnames=("t", "q", "ch", "g"))
def _nominate(store, valid, q0, *, t, q, ch, g):
    """Top-t (value, id) over valid store rows for store rows [q0, q0+q)."""
    qe = jax.lax.dynamic_slice_in_dim(store, q0, q)
    qids = q0 + jnp.arange(q, dtype=jnp.int32)

    def body(c, carry):
        rv, ri = carry
        blk = jax.lax.dynamic_slice_in_dim(store, c * ch, ch)
        ok = jax.lax.dynamic_slice_in_dim(valid, c * ch, ch)
        ids = c * ch + jnp.arange(ch, dtype=jnp.int32)
        s = jnp.dot(qe, blk.T, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        s = jnp.where(ok[None, :] & (ids[None, :] != qids[:, None]), s, -jnp.inf)
        v, p = top_t(s, t, g)
        idx = c * ch + p
        mv, mp = jax.lax.top_k(jnp.concatenate([rv, v], 1), t)
        return mv, jnp.take_along_axis(jnp.concatenate([ri, idx], 1), mp, 1)

    init = (jnp.full((q, t), -jnp.inf, jnp.float32), jnp.full((q, t), -1, jnp.int32))
    return jax.lax.fori_loop(0, store.shape[0] // ch, body, init)


def nominate_all(embn: np.ndarray, k: int) -> np.ndarray:
    """(N, k + margin) candidate ids of every row against all rows."""
    n, d = embn.shape
    t = k + lp.SELECT_MARGIN
    ch = min(NOMINATE_CHUNK, max(NOMINATE_GROUP * t, 1 << int(np.ceil(np.log2(max(n, 1))))))
    q = min(NOMINATE_QUERY, ch)
    cap = -ch * (-n // ch)
    dp = lp.dim_pad(d)
    host = np.zeros((cap, dp), np.float32)
    host[:n, :d] = embn
    store = jnp.asarray(host)
    valid = jnp.asarray(np.arange(cap) < n)
    outs = [_nominate(store, valid, jnp.int32(q0), t=t, q=q, ch=ch, g=NOMINATE_GROUP)
            for q0 in range(0, cap, q)]
    vals = np.concatenate([np.asarray(v) for v, _ in outs])[:n]
    ids = np.concatenate([np.asarray(i) for _, i in outs])[:n].astype(np.int64)
    del store
    return np.where(np.isfinite(vals), ids, -1)


@functools.partial(jax.jit, static_argnames=("max_iters",))
def jacobi(nbr, wgt, wl0, wl1, f0, tol, max_iters):
    """Full Jacobi sweeps of the weighted average until max |dF| <= tol."""
    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    wall = wgt.sum(axis=1) + wl0 + wl1

    def body(state):
        f, it, _ = state
        fv = jnp.where(mask, f[idx.T].T, 0.0)
        num = (wgt * fv).sum(axis=1) + wl1
        fn = jnp.where(wall > 0, num / jnp.maximum(wall, 1e-30), f)
        return fn.astype(f.dtype), it + 1, jnp.max(jnp.abs(fn - f)).astype(jnp.float32)

    def cond(state):
        _, it, d = state
        return (d > tol) & (it < max_iters)

    return jax.lax.while_loop(cond, body, (f0, jnp.int32(0), jnp.float32(jnp.inf)))


def solve(p: lp.Problem, f0: np.ndarray, tol: float, max_iters: int = 20000,
          dtype=jnp.float32):
    """Converged labels of the unlabelled rows, and the sweep count."""
    f, it, _ = jacobi(jnp.asarray(p.nbr), jnp.asarray(p.wgt, dtype), jnp.asarray(p.wl0, dtype),
                      jnp.asarray(p.wl1, dtype), jnp.asarray(f0, dtype),
                      jnp.float32(tol), max_iters)
    return np.asarray(f.astype(jnp.float32)), int(it)


def state_arrays(cfg: dict, data: Data) -> dict:
    """The preloaded graph state: every preload row alive, lists exact."""
    n0, k = data.n0, int(cfg["k"])
    t = [time.perf_counter()]
    emb = data.emb[:n0]
    embn = lp.normalize_rows(emb)
    cand = nominate_all(embn, k)
    t.append(time.perf_counter())
    ki, kw = lp.canonical_topk(embn, np.arange(n0), embn, cand, k)
    t.append(time.perf_counter())
    src, dst, wgt = lp.edges_from_lists(ki, kw)
    labels = data.labels0.copy()
    alive = np.ones(n0, bool)
    p = lp.build_problem(src, dst, wgt, labels, alive, 4 * k)
    t.append(time.perf_counter())
    f = np.where(labels == 1, 1.0, np.where(labels == 0, 0.0, 0.5)).astype(np.float32)
    fu, sweeps = solve(p, f[p.unl_ids], float(cfg["preload_tol"]))
    f[p.unl_ids] = fu
    t.append(time.perf_counter())
    d = np.diff(t)
    print(f"[preload] nominate {d[0]:.3f} s, canonical {d[1]:.3f} s, edges + problem "
          f"{d[2]:.3f} s, solve {d[3]:.3f} s ({sweeps} sweeps)", file=sys.stderr, flush=True)
    return {"emb": emb, "embn": embn, "labels": labels, "alive": alive, "f": f,
            "knn_idx": ki, "knn_wgt": kw, "src": src, "dst": dst, "wgt": wgt}
