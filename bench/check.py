"""The comparison that decides ``correct``.

The reference (``reference.RefGraph``) replays, from its own copy of the
preload, every window the service admitted, in commit order.  A window
is the set of write requests whose tickets name that commit, applied in
ticket order; the reference takes the requests from the generator, never
from the program.  Compared, each against its limit in the
configuration's ``limits``:

* ``graph_rows_differing``: rows whose final kNN list (ids and weight
  bytes) differs from the reference's, plus any difference in row count;
* ``edges_differing``: entries of the final edge arrays (src, dst, weight
  bytes) that differ from the reference's symmetrized lists;
* ``state_rows_differing``: rows whose label or liveness in a committed
  view differs from the reference at that commit;
* ``seed_f_differing``: alive labelled rows whose committed label value
  is not their label, at the checked commits;
* ``label_residual_max`` / ``label_residual_mean``: the largest |T(F) - F|
  of the committed labels over alive unlabelled rows, and the largest of
  its per-commit means, with T the weighted neighbourhood average on the
  reference's own graph, over the last commit and ``residual_commits``
  window commits drawn from the seed;
* ``read_answers_differing``: ids whose served (prediction, confidence)
  differs from the answer the reference derives from its labels and
  liveness at the ticket's commit and that commit's label values; every
  id of a read that never got an answer counts;
* ``writes_uncommitted``: write requests no commit ever made visible.
"""

from __future__ import annotations

import collections
import sys

import numpy as np

import lp
from reference import RefGraph, answers
from traffic import mutate_args

NUMBERS = ("writes_uncommitted", "graph_rows_differing", "edges_differing", "state_rows_differing",
           "seed_f_differing", "label_residual_max", "label_residual_mean",
           "read_answers_differing")


def windows(writes) -> dict[int, list]:
    """Commit id -> the write requests it made visible, in ticket order."""
    out = collections.defaultdict(list)
    for s in writes:
        if s.ticket.commit_id is not None:
            out[s.ticket.commit_id].append(s)
    for c in out:
        out[c].sort(key=lambda s: s.ticket.ticket)
    return out


def batch_args(sents, emb):
    """One window coalesced the way the service admits it."""
    parts = [mutate_args(s.write, emb) for s in sents]
    d = emb.shape[1]

    def cat(key, dtype, shape=(0,)):
        xs = [p[key] for p in parts if key in p]
        return np.concatenate(xs) if xs else np.zeros(shape, dtype)

    return dict(del_ids=cat("del_ids", np.int64), ins_emb=cat("ins_emb", np.float32, (0, d)),
                ins_labels=cat("ins_labels", np.int8), rel_ids=cat("rel_ids", np.int64),
                rel_labels=cat("rel_labels", np.int8))


def _rows_differing(ai, aw, bi, bw) -> int:
    n = min(len(ai), len(bi))
    same = (ai[:n] == bi[:n]).all(axis=1) & (
        aw[:n].view(np.int32) == bw[:n].view(np.int32)).all(axis=1)
    return int((~same).sum()) + abs(len(ai) - len(bi))


def _entries_differing(prog, ref) -> int:
    n = min(len(prog[0]), len(ref[0]))
    bad = np.zeros(n, bool)
    for a, b in zip(prog, ref):
        a, b = np.asarray(a)[:n], np.asarray(b)[:n]
        if a.dtype == np.float32:
            a, b = a.view(np.int32), b.view(np.int32)
        bad |= a != b
    return int(bad.sum()) + abs(len(prog[0]) - len(ref[0]))


def _describe(g, ref, n0):
    """Where the final lists differ: arriving or preloaded rows, ids or weights."""
    n = min(len(g["knn_idx"]), ref.num_nodes)
    ids = (g["knn_idx"][:n] != ref.knn_idx[:n]).any(axis=1)
    wts = (g["knn_wgt"][:n].view(np.int32) != ref.knn_wgt[:n].view(np.int32)).any(axis=1)
    bad = np.flatnonzero(ids | wts)
    print(f"[check] lists differ in {len(bad)} rows: {int((bad >= n0).sum())} arriving, "
          f"{int((bad < n0).sum())} preloaded; ids differ in {int(ids.sum())}, weights only "
          f"in {int((wts & ~ids).sum())}; first rows {bad[:4].tolist()}: program "
          f"{g['knn_idx'][bad[:2]].tolist()} reference {ref.knn_idx[bad[:2]].tolist()}",
          file=sys.stderr, flush=True)


def residual_check(ref: RefGraph, view) -> tuple[float, float, int, int]:
    """(max, mean residual, labelled rows off their label, state rows off)."""
    n = ref.num_nodes
    m = min(n, len(view.labels))
    state = int((view.labels[:m] != ref.labels[:m]).sum()
                + (view.alive[:m] != ref.alive[:m]).sum() + abs(len(view.labels) - n))
    f = np.full(n, np.nan, np.float32)
    f[:m] = view.f[:m]
    seeded = ref.alive & (ref.labels != lp.UNLABELLED)
    off = int((f[seeded] != ref.labels[seeded].astype(np.float32)).sum())
    p = ref.problem()
    r = lp.residuals(p, f[p.unl_ids])
    r = np.where(np.isfinite(r), r, np.inf)
    return (float(r.max()) if len(r) else 0.0, float(r.mean()) if len(r) else 0.0, off,
            state)


def compare(cfg: dict, state: dict, emb: np.ndarray, rec: dict, sample_word: int,
            ref_kw: dict | None = None) -> dict[str, float]:
    """Replay and compare; returns every number that was measured."""
    k = int(cfg["k"])
    by_commit = windows(rec["writes"])
    last = max(by_commit) if by_commit else 0
    missing = [c for c in range(1, last + 1) if c not in by_commit]
    if missing:
        raise RuntimeError(f"commits with no requests: {missing[:5]}")
    out = collections.Counter()
    out["writes_uncommitted"] = sum(s.ticket.commit_id is None for s in rec["writes"])
    reads = collections.defaultdict(list)
    for s in rec["reads"]:
        if s.ticket.result is None:
            out["read_answers_differing"] += len(s.ids)
        else:
            reads[s.ticket.result.commit_id].append(s)
    views = rec["views"]
    rng = np.random.default_rng(sample_word)
    window_commits = [c for c in rec["window_commits"] if c in views]
    checked = set(rng.choice(window_commits, size=min(len(window_commits),
                                                      int(cfg["residual_commits"])),
                             replace=False).tolist()) if window_commits else set()
    checked.add(last)
    ref = RefGraph(state, k, capacity=len(emb), **(ref_kw or {}))
    res_max, res_mean = 0.0, 0.0

    def at_commit(c):
        nonlocal res_max, res_mean
        view = views.get(c)
        if c in reads:
            for s in reads[c]:
                pred, conf = answers(ref.labels, ref.alive, view.f, s.ids)
                r = s.ticket.result
                out["read_answers_differing"] += int(
                    ((r.pred != pred) | (r.confidence.view(np.int32) != conf.view(np.int32))).sum())
        if c in checked:
            mx, mean, off, st = residual_check(ref, view)
            res_max = max(res_max, mx)
            res_mean = max(res_mean, mean)
            out["seed_f_differing"] += off
            out["state_rows_differing"] += st
        elif view is not None:
            n = min(ref.num_nodes, len(view.labels))
            out["state_rows_differing"] += int(
                (view.labels[:n] != ref.labels[:n]).sum()
                + (view.alive[:n] != ref.alive[:n]).sum()
                + abs(len(view.labels) - ref.num_nodes))

    at_commit(0)
    for c in range(1, last + 1):
        ref.apply(**batch_args(by_commit[c], emb))
        at_commit(c)
    g = rec["graph"]
    out["graph_rows_differing"] = _rows_differing(g["knn_idx"], g["knn_wgt"],
                                                  ref.knn_idx, ref.knn_wgt)
    if out["graph_rows_differing"]:
        _describe(g, ref, len(state["labels"]))
    out["edges_differing"] = _entries_differing((g["src"], g["dst"], g["wgt"]), ref.edges())
    nums = {n: float(out[n]) for n in NUMBERS}
    nums["label_residual_max"] = res_max
    nums["label_residual_mean"] = res_mean
    if not rec["reads"]:
        del nums["read_answers_differing"]
    return nums
