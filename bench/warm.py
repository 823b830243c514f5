"""Set-up that builds, before the measured window, every program the
cell's traffic can make the service compile.

The service compiles some programs per exact size rather than per rung
of a ladder, so a short warm-up on the traffic itself would leave sizes
that first show up inside the window:

* the read path slices each fused gather's answer to the number of ids
  it holds (``DeviceLabelView.query``), one program per fused size, and
  a fused size is ``read_ids`` times the tickets that queued while the
  driver thread was busy;
* the ingest store refreshes the k-th weights of a window's changed
  rows on a doubling ladder of row counts that a short warm-up may not
  reach from below;
* the supernode step of ``StreamEngine.submit`` runs eager operations
  and ``connected_components`` on arrays of the window's insert count
  and of its unlabelled inserts, which are the same count where no
  window relabels a row it inserts (the mixes relabel preloaded rows).

The sizes a window can reach follow from the mix alone: fused reads of
``read_ids`` x 1, 2, ... up to ``warmup_read_ids`` ids, and insert counts
of ``ops_per_request`` x 1, 2, ... up to the count that a window of
``window_ops`` ops, its requests drawn by the mix's shares, exceeds with
a chance under ``MISS``.
"""

from __future__ import annotations

import math
import time
from types import SimpleNamespace

import numpy as np

import traffic

MISS = 1e-9  # chance a window holds more insert requests than are warmed
KTH_ROWS = 16384  # changed rows per window: a burst of 1,024 deletes repairs ~5,000
# G' (edges among a window's new rows) is empty, or lies on the first
# rung of the neighbour axis (``snapshot.bucket_k``: degree 1-8): a new
# row is in another new row's k nearest with a chance near k / rows, so
# a degree past 8 among a window's few hundred arrivals does not occur
GPRIME_DEGREES = (0, 1)


def bursts(svc, drv, writes: list, emb) -> None:
    """Commit each burst of write requests as one window (inserts reach
    the ingest batch rungs, deletes the rungs of killed and repaired
    rows).  The bursts' writes are kept with the run's writes, so the
    reference replays them."""
    for burst in writes:
        for w in burst:
            now = time.perf_counter()
            s = traffic.Sent(now, now, write=w)
            s.ticket = svc.mutate(**traffic.mutate_args(w, emb))
            drv.writes.append(s)
        svc.sync()


def reads(svc, mix: dict) -> None:
    """One read of every fused size the reader can make, through the
    served path: a ticket of q ids takes the same programs as q / read_ids
    tickets fused into one gather."""
    step = int(mix.get("read_ids", 0))
    top = int(mix.get("warmup_read_ids", 0))
    if not step:
        return
    for q in range(step, top + 1, step):
        ticket = svc.query_async(np.zeros(q, np.int64))
        if ticket is None:
            raise RuntimeError("read refused: the service driver is not running")
        ticket.wait(600)


def kth_rungs(graph, ingestor, top: int = KTH_ROWS) -> None:
    """Refresh the k-th weights of the first 8, 16, ... ``top`` rows to the
    values they hold, as ``DynamicGraph.apply_batch`` does for a window's
    changed rows: each rung of the store's row ladder is built, and the
    state is left as it was.  Call before any row is deleted."""
    n = 8
    while n <= min(top, graph.num_nodes):
        rows = np.arange(n, dtype=np.int64)
        ingestor.finalize(graph, rows, graph.kth_weights(rows))
        n *= 2


def insert_requests(mix: dict) -> int:
    """The most insert requests a window holds, but with a chance under
    ``MISS``: its requests are drawn one by one by the mix's shares."""
    n = int(mix["service"]["window_ops"]) // int(mix["ops_per_request"])
    shares = mix["write_mix"]
    p = shares.get("insert", 0) / sum(shares.values())
    tail = 1.0
    for j in range(n + 1):
        tail -= math.comb(n, j) * p**j * (1 - p) ** (n - j)
        if tail < MISS:
            return j
    return n


def supernode(mix: dict) -> None:
    """The supernode step of ``StreamEngine.submit`` (Alg. 2 Step 2) at
    every insert count a window can hold, with G' empty and with G' on
    the first neighbour rung, called as ``submit`` calls it."""
    import jax.numpy as jnp

    from repro.core.components import compact_labels
    from repro.core.dynlp import gprime_components
    from repro.core.init_labels import supernode_init

    step = int(mix["ops_per_request"])
    for m in range(step, step * insert_requests(mix) + 1, step):
        for deg in GPRIME_DEGREES:
            src = np.zeros(deg, np.int64)
            dst = np.arange(1, deg + 1, dtype=np.int64)
            effect = SimpleNamespace(gprime_src=src, gprime_dst=dst,
                                     gprime_wgt=np.ones(deg, np.float32))
            comp_local = gprime_components(effect, m)
            local_idx = np.arange(m, dtype=np.int64)
            comp = compact_labels(jnp.asarray(comp_local))[local_idx]
            int(jnp.max(comp) + 1)
            wl = np.zeros(m, np.float32)
            np.asarray(supernode_init(comp, jnp.asarray(wl), jnp.asarray(wl),
                                      num_segments=max(m, 1)))
