"""LP serving benchmark: open-loop read load against the async service.

Drives ``serving.lp_service.LPService`` with its background driver
running (queries fused into jitted device gathers against the committed
``DeviceLabelView``; mutations coalesced per admission window and
pipelined through ``StreamEngine.submit``/``poll`` by the driver's
clock) under two phases per arm:

  * **open-loop** — reads arrive on a FIXED schedule (``OFFERED_QPS``)
    while a writer thread replays the full mutation stream; each
    latency is measured from the read's *scheduled arrival* to its
    fulfilment, so queueing delay behind slow windows is charged to the
    service instead of silently self-throttling the load generator (the
    closed-loop caller of the pre-async benchmark had exactly that
    coordinated-omission bug).  Gated by per-arm p99 SLO floors.
  * **saturation** — after the writer drains, reads are issued
    back-to-back with a bounded number of outstanding tickets against
    the QUIESCENT service; sustained ``node_lookups_per_sec`` is the
    headline (floor: 100x the host-indexing read path this replaced,
    ``LOOKUPS_FLOOR``).  Quiescence matters for the sharded/single
    comparison: a concurrent writer would charge the sharded arm its
    (much larger, virtual-device-multiplied) commit HOST cost against
    read throughput, measuring writer CPU rather than read capacity.

Arms:

  * ``serve``          — single-device StreamEngine under the service;
  * ``serve_sharded``  — engine row-sharded over the visible devices
                         (``REPRO_FORCE_HOST_DEVICES=8`` forces an
                         8-virtual-device CPU mesh, decided before jax
                         initializes; the CI bench-smoke job does this)
                         with reads served from the mesh's spare device
                         (``core.distributed.read_replica_device``) so
                         gathers never queue behind solve programs.

Arms run as interleaved best-of-``ROUNDS`` (the stream_throughput
precedent: kills one-sided CI drift).  ``--check`` hard-asserts the
serving contract — overlap, commits, compile bounds, the lookup floor,
the open-loop p99 floor, and sharded-vs-single: strictly faster at full
scale, where replica isolation outweighs mesh staging overhead; bounded
below by ``SHARDED_RATIO_FLOOR`` under ``--tiny``, whose ~5 ms solves
leave the mechanism inside measurement noise (docs/benchmarks.md).
``--tiny`` shrinks the stream for CI smoke runs.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

# Must run before jax initializes: virtual CPU devices for the sharded arm.
_force = os.environ.get("REPRO_FORCE_HOST_DEVICES")
if _force:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_force}"
    ).strip()

import jax
import numpy as np

try:
    from benchmarks.common import check_gate as _gate, finish_checks
except ImportError:  # run as a script: sys.path[0] is benchmarks/ itself
    from common import check_gate as _gate, finish_checks

from repro.core.snapshot import ladder_size
from repro.core.stream import StreamEngine
from repro.data.synth import StreamSpec, gaussian_mixture_stream
from repro.graph.dynamic import DynamicGraph
from repro.kernels import ops
from repro.launch.mesh import make_stream_mesh
from repro.serving.lp_service import LPService

OUT = "BENCH_serve.json"
DELTA = 1e-3  # match stream_throughput: measure machinery, not solve depth

SPEC = dict(total_vertices=3000, batch_size=60, seed=0,
            class_sep=6.0, noise=0.9, frac_deleted=0.09)
TINY = dict(total_vertices=600, batch_size=60, seed=0,
            class_sep=6.0, noise=0.9, frac_deleted=0.09)

QUERY_BURST = 64  # node ids per open-loop query
OFFERED_QPS = 300.0  # open-loop arrival rate (fixed schedule)
SAT_BURST = 4096  # node ids per saturation ticket
SAT_OUTSTANDING = 32  # max unfulfilled saturation tickets
SAT_SECONDS = {True: 4.0, False: 6.0}  # keyed by tiny
ROUNDS = {True: 3, False: 3}
MUTATIONS_PER_BATCH = 4  # each stream batch arrives as this many mutations
WRITER_PAUSE_S = 0.015  # gap between stream batches: longer than
# window_ms, so the partial window left at a batch boundary is admitted
# by the DRIVER's deadline clock, not by the next mutation's size check

# Recorded floors for --check.  The lookup floor is 100x the PR-5
# committed number for the host-indexing read path this PR replaced
# (5816.1 node lookups/sec): fused jitted gathers clear it by orders of
# magnitude, so tripping it means the read path regressed back into
# per-call host work.  The p99 floors bound OPEN-LOOP latency
# (scheduled arrival -> fulfilment, queueing included) PER ARM: the
# single arm's tail is the gather ladder's compile stalls (the graph
# grows through node buckets DURING the open-loop phase, and a read
# scheduled behind a fresh rung's jit compile is charged its wait); the
# sharded arm's tail additionally queues behind commit stalls that a
# forced 8-virtual-device mesh multiplies on shared host cores.  The
# floors bound those tails, they do not pretend them away.  The sharded
# ratio floor guards the PR-5 regression ("sharded 2x slower"); at full
# scale the check is strict (> 1).
PR5_NODE_LOOKUPS_PER_SEC = 5816.1
LOOKUPS_FLOOR = 100.0 * PR5_NODE_LOOKUPS_PER_SEC
OPEN_LOOP_P99_MS_FLOOR = {"serve": 350.0, "serve_sharded": 2500.0}
COMMIT_P95_MS_FLOOR = 30_000.0
# the per-arm LOOKUPS_FLOOR catches a read path regressing to host
# work outright; the tiny ratio floor specifically guards the sharded
# arm being left behind (PR-5 measured 0.47x).  It is deliberately
# loose: saturated gather rates on shared CI cores swing ~±20%
# between best-of-3 rounds, and a floor inside that band would flake.
SHARDED_RATIO_FLOOR = 0.75


def _pct(xs) -> dict:
    """Latency percentiles; {} on empty samples (a zero-query phase must
    not crash the report)."""
    if xs is None or not len(xs):
        return {}
    arr = np.asarray(xs)
    return {"p50": round(float(np.percentile(arr, 50)), 4),
            "p95": round(float(np.percentile(arr, 95)), 4),
            "p99": round(float(np.percentile(arr, 99)), 4),
            "max": round(float(arr.max()), 4)}


class _Writer(threading.Thread):
    """Replays stream batches through ``mutate`` as fast as the service
    admits them (the driver's clock handles windows and commits)."""

    def __init__(self, svc: LPService, batches: list):
        super().__init__(daemon=True)
        self.svc = svc
        self.batches = batches
        self.tickets = []  # every MutationTicket, for commit latency
        self.done = threading.Event()

    def run(self):
        for batch in self.batches:
            n = len(batch.ins_emb)
            cuts = [(i * n) // MUTATIONS_PER_BATCH
                    for i in range(MUTATIONS_PER_BATCH + 1)]
            self.tickets.append(self.svc.mutate(
                ins_emb=batch.ins_emb[:cuts[1]],
                ins_labels=batch.ins_labels[:cuts[1]],
                del_ids=batch.del_ids))
            for a, b in zip(cuts[1:], cuts[2:]):
                if b > a:
                    self.tickets.append(self.svc.mutate(
                        ins_emb=batch.ins_emb[a:b],
                        ins_labels=batch.ins_labels[a:b]))
            time.sleep(WRITER_PAUSE_S)
        self.done.set()


def _open_loop(svc: LPService, rng, writer: _Writer) -> dict:
    """Fixed-schedule read load while the writer streams; latency from
    each read's SCHEDULED arrival (coordinated-omission-free)."""
    period = 1.0 / OFFERED_QPS
    pending: list[tuple[object, float]] = []
    t0 = time.perf_counter()
    i = 0
    while not writer.done.is_set():
        sched = t0 + i * period
        now = time.perf_counter()
        if now < sched:
            time.sleep(sched - now)
        hi = max(1, svc.committed_view().num_nodes)
        t = svc.query_async(rng.integers(0, hi, QUERY_BURST))
        pending.append((t, sched))
        i += 1
    elapsed = time.perf_counter() - t0
    lat = []
    for t, sched in pending:
        t.wait(60.0)
        lat.append((t.completed_at - sched) * 1e3)
    return {
        "offered_qps": OFFERED_QPS,
        "queries": len(pending),
        "elapsed_s": round(elapsed, 3),
        "achieved_qps": round(len(pending) / elapsed, 1),
        "latency_ms": _pct(lat),
    }


def _saturate(svc: LPService, rng, seconds: float) -> dict:
    """Back-to-back reads with bounded outstanding tickets against the
    drained service; sustained node lookups/sec is the headline."""
    lookups = 0
    outstanding: list = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        hi = max(1, svc.committed_view().num_nodes)
        outstanding.append(svc.query_async(rng.integers(0, hi, SAT_BURST)))
        if len(outstanding) >= SAT_OUTSTANDING:
            head = outstanding.pop(0)
            head.wait(60.0)
            lookups += len(head.ids)
    for t in outstanding:
        t.wait(60.0)
        lookups += len(t.ids)
    elapsed = time.perf_counter() - t0
    return {
        "burst": SAT_BURST,
        "lookups": lookups,
        "elapsed_s": round(elapsed, 3),
        "node_lookups_per_sec": round(lookups / elapsed, 1),
    }


def _run_serve(spec: StreamSpec, mesh=None, tiny: bool = False) -> dict:
    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng = StreamEngine(g, delta=DELTA, mesh=mesh)
    # window_ops does not divide a batch's op count, so batch boundaries
    # leave a partial window open for WRITER_PAUSE_S > window_ms — those
    # admissions MUST come from the driver's deadline clock
    svc = LPService(eng, window_ops=spec.batch_size * 3 // 4, window_ms=10.0,
                    max_pending_ops=spec.batch_size * 8)
    rng = np.random.default_rng(7)
    batches = [b for b, _ in gaussian_mixture_stream(spec)]
    t0 = time.perf_counter()
    with svc:
        # phase 1: open-loop latency while the whole stream lands
        writer = _Writer(svc, batches)
        writer.start()
        open_loop = _open_loop(svc, rng, writer)
        writer.join()
        svc.sync()
        # phase 2: saturation throughput against the quiescent service
        saturation = _saturate(svc, rng, SAT_SECONDS[tiny])
        elapsed = time.perf_counter() - t0
        st = svc.stats()
    max_k = max(k for _, k in eng.bucket_keys)
    out = {
        "batches": eng.batches,
        "mutations": st.mutations,
        "ops_accepted": st.ops_accepted,
        "batches_admitted": st.batches_admitted,
        "batches_committed": st.batches_committed,
        "deadline_admissions": st.deadline_admissions,
        "queries": st.queries,
        "query_nodes": st.query_nodes,
        "queries_while_inflight": st.queries_while_inflight,
        "read_batches": st.read_batches,
        "read_tickets": st.read_tickets,
        "elapsed_s": round(elapsed, 3),
        "mutation_ops_per_sec": round(st.ops_accepted / elapsed, 1),
        "open_loop": open_loop,
        "saturation": saturation,
        "node_lookups_per_sec": saturation["node_lookups_per_sec"],
        # enqueue -> commit of every mutation (all committed after sync)
        "mutation_commit_latency_ms": _pct(
            [t.latency_ms for t in writer.tickets]),
        "recompiles": st.recompiles,
        "bucket_rungs": st.bucket_rungs,
        "ladder_bound": ladder_size(spec.total_vertices + 256, max_k),
    }
    if mesh is not None:
        out["mesh_devices"] = int(mesh.devices.size)
        out["plan_builds"] = eng.plan_builds
        out["transport"] = st.transport  # per-rung modes + halo traffic
    return out


def _check_arm(name: str, r: dict):
    """The serving contract + recorded floors for one arm."""
    _gate(f"{name}/overlap", r["queries_while_inflight"] > 0,
          "no query was served while a solve was in flight")
    _gate(f"{name}/deadline", r["deadline_admissions"] > 0,
          "the driver's deadline clock never admitted a window — "
          "admission depended on caller traffic")
    _gate(f"{name}/commits",
          r["batches_admitted"] == r["batches_committed"],
          f"{r['batches_admitted']} admitted != "
          f"{r['batches_committed']} committed")
    _gate(f"{name}/recompiles", r["recompiles"] <= r["ladder_bound"],
          f"{r['recompiles']} recompiles > ladder {r['ladder_bound']}")
    _gate(f"{name}/lookups",
          r["node_lookups_per_sec"] >= LOOKUPS_FLOOR,
          f"{r['node_lookups_per_sec']} node lookups/s < floor "
          f"{LOOKUPS_FLOOR} (100x the host read path)")
    p99 = r["open_loop"]["latency_ms"].get("p99", 0.0)
    floor = OPEN_LOOP_P99_MS_FLOOR[name]
    _gate(f"{name}/open_loop_p99", p99 <= floor,
          f"open-loop p99 {p99} ms > floor {floor} ms")
    _gate(f"{name}/commit_p95",
          r["mutation_commit_latency_ms"].get("p95", 0)
          <= COMMIT_P95_MS_FLOOR,
          f"commit p95 {r['mutation_commit_latency_ms'].get('p95')} "
          f"ms > floor {COMMIT_P95_MS_FLOOR} ms")
    if "plan_builds" in r:
        # halo export-budget overflows build the rung's all-gather twin
        # too — allow one extra plan per overflow
        bound = r["bucket_rungs"] + r["transport"]["overflows"]
        _gate(f"{name}/plan_builds", r["plan_builds"] <= bound,
              f"{r['plan_builds']} plans > {r['bucket_rungs']} "
              f"rungs + {r['transport']['overflows']} overflows")


def main(out: str = OUT, tiny: bool = False, check: bool = False) -> dict:
    n_dev = len(jax.devices())
    # serving mesh: one device stays OUT of the solver mesh as the read
    # replica (core.distributed.read_replica_device) — query gathers then
    # never share an execution stream with solves or snapshot staging.
    # A full-width mesh would instead publish views row-sharded, paying a
    # per-gather collective (docs/serving.md §Sharded serving).
    mesh = make_stream_mesh(max(n_dev - 1, 1)) if n_dev > 1 else None
    spec = StreamSpec(**(TINY if tiny else SPEC))
    arm_specs = {"serve": None}
    if mesh is not None:
        arm_specs["serve_sharded"] = mesh
    # interleaved best-of-rounds: scheduler/CI drift hits both arms
    # alike instead of whichever ran second.  The two phases are
    # INDEPENDENT measurements and jitter hits them independently, so
    # each phase's best round is recorded on its own — saturation by
    # lookups/s, open-loop by p99 (a round that saturates best can
    # still carry a one-off stall in its open-loop tail).
    rounds = ROUNDS[tiny]
    best: dict[str, dict] = {}
    best_ol: dict[str, dict] = {}
    history: dict[str, list] = {k: [] for k in arm_specs}
    history_ol: dict[str, list] = {k: [] for k in arm_specs}
    for _ in range(rounds):
        for name, m in arm_specs.items():
            r = _run_serve(spec, mesh=m, tiny=tiny)
            history[name].append(r["node_lookups_per_sec"])
            p99 = r["open_loop"]["latency_ms"].get("p99", float("inf"))
            history_ol[name].append(p99)
            if (name not in best
                    or r["node_lookups_per_sec"]
                    > best[name]["node_lookups_per_sec"]):
                best[name] = r
            if (name not in best_ol
                    or p99 < best_ol[name]["latency_ms"].get(
                        "p99", float("inf"))):
                best_ol[name] = r["open_loop"]
    for name in best:
        best[name]["open_loop"] = best_ol[name]
    results = {
        "backend_auto_resolves_to": ops.select_backend("auto"),
        "devices": n_dev,
        "sharded_arm": mesh is not None,
        "rounds": rounds,
        "query_burst": QUERY_BURST,
        "offered_qps": OFFERED_QPS,
        "floors": {"node_lookups_per_sec": LOOKUPS_FLOOR,
                   "open_loop_p99_ms": dict(OPEN_LOOP_P99_MS_FLOOR),
                   "commit_p95_ms": COMMIT_P95_MS_FLOOR,
                   "sharded_ratio_tiny": SHARDED_RATIO_FLOOR},
        "lookups_per_round": history,
        "open_loop_p99_per_round": history_ol,
    }
    results.update(best)
    for name, r in best.items():
        ol = r["open_loop"]
        print(f"{name}: {r['node_lookups_per_sec']:.0f} node lookups/s "
              f"saturated | open-loop {ol['achieved_qps']:.0f}/"
              f"{ol['offered_qps']:.0f} q/s, p50/p99 "
              f"{ol['latency_ms'].get('p50')}/{ol['latency_ms'].get('p99')} "
              f"ms | {r['mutation_ops_per_sec']:.0f} mutation ops/s | "
              f"{r['queries_while_inflight']}/{r['queries']} reads "
              f"mid-flight | {r['deadline_admissions']} deadline admissions "
              f"| commit p50/p95 {r['mutation_commit_latency_ms'].get('p50')}"
              f"/{r['mutation_commit_latency_ms'].get('p95')} ms | "
              f"{r['recompiles']} recompiles ≤ ladder {r['ladder_bound']}")
        if check:
            _check_arm(name, r)
    if mesh is not None and check:
        ratio = (best["serve_sharded"]["node_lookups_per_sec"]
                 / max(best["serve"]["node_lookups_per_sec"], 1e-9))
        results["sharded_over_single"] = round(ratio, 3)
        if tiny:
            # ~5 ms tiny solves put replica isolation inside the noise:
            # gate only the PR-5 "2x slower" regression here; the strict
            # comparison is a full-scale property (docs/benchmarks.md)
            _gate("sharded/ratio", ratio >= SHARDED_RATIO_FLOOR,
                  f"sharded/single lookup ratio {ratio:.3f} < "
                  f"{SHARDED_RATIO_FLOOR}")
        else:
            _gate("sharded/strictly_faster", ratio > 1.0,
                  f"sharded/single lookup ratio {ratio:.3f} — replica "
                  "reads should beat single-device at full scale")
    elif mesh is not None:
        results["sharded_over_single"] = round(
            best["serve_sharded"]["node_lookups_per_sec"]
            / max(best["serve"]["node_lookups_per_sec"], 1e-9), 3)
    with open(out, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"wrote {os.path.abspath(out)}")
    if check:
        finish_checks()
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 600-vertex stream")
    ap.add_argument("--check", action="store_true",
                    help="assert overlap + floors + compile-once contract")
    ap.add_argument("--out", default=OUT, help="output JSON path")
    args = ap.parse_args()
    main(out=args.out, tiny=args.tiny, check=args.check)
