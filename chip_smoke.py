"""Bring the served label-propagation path up on a TPU and check its answers.

    python chip_smoke.py              # one chip: the served path at full size
    python chip_smoke.py --chips 4    # four chips: sharded engine vs one chip

The deployment has ogbn-arxiv's shape (OGB node-property prediction):
169,343 nodes with 128-d features, 90,941 of them labelled (the public
training split), the 40 classes collapsed to 2.  Embeddings come from
``data.synth`` (two-Gaussian mixture) seeded by ``--seed``.  By default
the smoke loads 49,152 of the rows (``--rows`` sets the count; the width
and the labelled fraction never change): host staging and the host
oracle replay cost seconds per 1,024-row window whatever the store size,
and the full 186 windows would not fit a 20-minute run with both.

One chip: the rows load through ``LPService.add_points`` in 1,024-row
admission windows with the background driver running, 20 mixed windows
follow (inserts, 5% deletes of live ids, relabels), then fused reads of
4,096 ids and ``sync()``.  The engine is ``StreamEngine(ingest="device")``:
``DeviceIngestor`` runs the argkmin kernel over the device embedding store,
the backend registry picks each rung's propagation, and reads gather from
the committed ``DeviceLabelView``.  Checks, against plain references fed
the same admitted windows:

  * the device-ingested graph (``knn_idx``, ``knn_wgt``, edge list) is
    byte-identical to the host-staging oracle (``DynLP``'s host kNN);
  * committed labels match a per-window ``DynLP`` recompute: bit-identical
    where every rung ran ``ref``, allclose where a Pallas backend ran;
  * fused device reads equal the host view's answers;
  * the ``bsr`` MXU kernel, compiled, stays allclose to ``ref`` on a
    small clustered stream (the deployment's rungs are too sparse for
    auto to pick it).

Four chips (``--chips 4``, only this phase): the same stream runs through
``StreamEngine(mesh=make_stream_mesh(4))`` with its row-sharded store and
through a one-chip engine in the same process; labels and graphs must be
bit-identical, and the store must really span four shards.

Exits non-zero, printing no result line, if no TPU is found, a phase
raises, the service driver failed, a Pallas kernel would run interpreted,
or a check fails.  Wall seconds printed per phase are smoke timings, not
metrics.  The last stdout line is the JSON result.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

ROWS = 169_343  # ogbn-arxiv nodes
LABELLED = 90_941  # its public training split
DEFAULT_ROWS = 49_152  # the row cut (see the module docstring)
DIM = 128
K = 5
WINDOW = 1024
MIXED_WINDOWS = 20
DEL_FRAC, REL_FRAC = 0.05, 0.10
READ_IDS, READ_TICKETS = 4096, 8
BSR_ATOL = 2e-3  # registry contract for the Pallas backends vs ref
BSR_ROWS = 4096  # the bsr check's small clustered stream

TIMINGS: dict[str, float] = {}


def say(*parts):
    print(*parts, flush=True)


@contextlib.contextmanager
def phase(name):
    t0 = time.perf_counter()
    yield
    TIMINGS[name] = time.perf_counter() - t0
    say(f"[phase] {name}: {TIMINGS[name]:.3f} s wall (smoke timing, not a metric)")


def check(cond, what):
    if not cond:
        raise AssertionError(f"check failed: {what}")
    say(f"[check] {what}: ok")


# --------------------------------------------------------------------- #
# the stream: load windows, then mixed windows (one mutation per window)
# --------------------------------------------------------------------- #
def make_stream(rows, seed):
    """List of per-window mutations: dicts of LPService.mutate kwargs."""
    from repro.data.synth import StreamSpec, gaussian_mixture_stream
    from repro.graph.dynamic import UNLABELED

    spec = StreamSpec(total_vertices=rows, batch_size=WINDOW, emb_dim=DIM,
                      frac_labeled=LABELLED / ROWS, frac_deleted=0.0,
                      seed=seed)
    windows = [dict(ins_emb=b.ins_emb, ins_labels=b.ins_labels)
               for b, _ in gaussian_mixture_stream(spec)]
    n_del, n_rel = int(DEL_FRAC * WINDOW), int(REL_FRAC * WINDOW)
    n_ins = WINDOW - n_del - n_rel
    more = StreamSpec(total_vertices=n_ins * MIXED_WINDOWS, batch_size=n_ins,
                      emb_dim=DIM, frac_labeled=LABELLED / ROWS,
                      frac_deleted=0.0, seed=seed + 1)
    rng = np.random.default_rng(seed + 2)
    alive = np.ones(rows, bool)
    for b, _ in gaussian_mixture_stream(more):
        live = np.flatnonzero(alive)
        pick = rng.choice(live, n_del + n_rel, replace=False)
        dels, rels = pick[:n_del], pick[n_del:]
        windows.append(dict(
            ins_emb=b.ins_emb, ins_labels=b.ins_labels, del_ids=dels,
            rel_ids=rels,
            rel_labels=rng.choice(np.array([0, 1, UNLABELED], np.int8), n_rel)))
        alive[dels] = False
        alive = np.concatenate([alive, np.ones(n_ins, bool)])
    return windows, len(windows) - MIXED_WINDOWS


def as_batch(w):
    from repro.graph.dynamic import BatchUpdate

    z = np.zeros(0, np.int64)
    return BatchUpdate(ins_emb=w["ins_emb"], ins_labels=w["ins_labels"],
                       del_ids=w.get("del_ids", z), rel_ids=w.get("rel_ids", z),
                       rel_labels=w.get("rel_labels", np.zeros(0, np.int8)))


def serve(windows, n_load, mesh=None, reads=True):
    """Run the stream through LPService with its background driver."""
    from repro.core.stream import StreamEngine
    from repro.graph.dynamic import DynamicGraph
    from repro.ingest import ingest_cache_size
    from repro.kernels import argkmin
    from repro.kernels.platform import resolve_interpret
    from repro.serving.lp_service import LPService

    g = DynamicGraph(emb_dim=DIM, k=K)
    eng = StreamEngine(g, ingest="device", mesh=mesh)
    ing = eng.ingestor
    check(argkmin.resolve_backend(ing.backend) == "pallas"
          and not resolve_interpret(ing.interpret)
          and not resolve_interpret(eng.interpret),
          "argkmin resolves to the compiled Pallas pass, no kernel interpreted")
    svc = LPService(eng, window_ops=WINDOW, max_pending_ops=2 * WINDOW)
    c0 = ingest_cache_size()
    with svc:
        with phase("load" if mesh is None else "load (mesh)"):
            t0 = time.perf_counter()
            for i, w in enumerate(windows[:n_load]):
                svc.mutate(**w)
                if i % 32 == 31:
                    say(f"[load] {i + 1} windows, {g.num_nodes} rows, "
                        f"{time.perf_counter() - t0:.1f} s")
            svc.sync()
        with phase("mixed windows" if mesh is None else "mixed (mesh)"):
            for w in windows[n_load:]:
                svc.mutate(**w)
            svc.sync()
        if reads:
            with phase("fused reads"):
                rng = np.random.default_rng(7)
                ids = [rng.integers(0, g.num_nodes, READ_IDS)
                       for _ in range(READ_TICKETS)]
                tickets = [svc.query_async(q) for q in ids]
                check(all(t is not None for t in tickets),
                      "reads go through the running driver")
                res = [t.wait(timeout=600) for t in tickets]
                svc.sync()
            view = eng.committed_view()
            same = all(
                r.commit_id == view.commit_id
                and np.array_equal(r.pred, view.query(q)[0])
                and np.array_equal(r.confidence, view.query(q)[1])
                for r, q in zip(res, ids))
            check(same, f"{READ_TICKETS} fused device reads of {READ_IDS} "
                        "ids equal the host view")
            st = svc.stats()
            say(f"[reads] tickets {st.read_tickets} in {st.read_batches} "
                "fused gathers")
    st = svc.stats()
    check(st.batches_admitted == st.batches_committed == len(windows),
          f"one admitted batch per window ({len(windows)})")
    summary = eng.transport_summary()
    say(f"[argkmin] path: {argkmin.resolve_backend(ing.backend)}, interpret "
        f"{resolve_interpret(ing.interpret)}, "
        f"store shards {ing.store.n_shards}, capacity {ing.store.capacity}")
    for rung, be in summary["rung_backends"].items():
        mode = summary["rung_modes"].get(rung, "single")
        say(f"[rung] {rung}: backend {be}, transport {mode}")
    say(f"[stream] rows {g.num_nodes} (alive {g.num_alive}), windows "
        f"{len(windows)}, solve recompiles {eng.recompile_count} over "
        f"{len(eng.bucket_keys)} rungs, ingest jit entries "
        f"{ingest_cache_size() - c0}, bsr batches {eng.bsr_batches}")
    return g, eng


def fingerprint(g):
    return [getattr(g, a).tobytes()
            for a in ("knn_idx", "knn_wgt", "src", "dst", "wgt")]


def one_chip(args):
    from repro.core.dynlp import DynLP
    from repro.graph.dynamic import DynamicGraph

    with phase("data"):
        windows, n_load = make_stream(args.rows, args.seed)
    say(f"[data] {args.rows} rows x {DIM}-d, {n_load} load windows + "
        f"{MIXED_WINDOWS} mixed windows of {WINDOW} ops, seed {args.seed}")
    g, eng = serve(windows, n_load)
    say(f"[stream] wall seconds per window (host clock): "
        f"{(TIMINGS['load'] + TIMINGS['mixed windows']) / len(windows):.4f} "
        "(smoke timing, not a metric)")

    with phase("oracle"):
        g_ref = DynamicGraph(emb_dim=DIM, k=K)
        dyn = DynLP(g_ref, delta=eng.delta, max_iters=eng.max_iters,
                    backend="ref")
        t0 = time.perf_counter()
        for i, w in enumerate(windows):
            dyn.step(as_batch(w))
            if i % 32 == 31:
                say(f"[oracle] {i + 1} windows, "
                    f"{time.perf_counter() - t0:.1f} s")
    check(fingerprint(g) == fingerprint(g_ref),
          "device-ingested graph byte-identical to the host-staging oracle "
          f"({g.num_edges} undirected edges)")
    check(np.array_equal(g.labels, g_ref.labels)
          and np.array_equal(g.alive, g_ref.alive),
          "labels and liveness match the oracle")
    backends = set(eng.transport_summary()["rung_backends"].values())
    diff = float(np.abs(g.f - g_ref.f).max()) if g.num_nodes else 0.0
    if backends <= {"ref"}:
        check(np.array_equal(g.f, g_ref.f),
              "committed labels bit-identical to the DynLP recompute (ref)")
    else:
        check(diff <= BSR_ATOL, f"committed labels within {BSR_ATOL} of the "
                                f"DynLP recompute (rungs {sorted(backends)}, "
                                f"max diff {diff:.3g})")
    ids, pred = eng.predictions()
    _, pred_ref = dyn.predictions()
    say(f"[labels] {len(ids)} unlabelled rows, max |f - f_ref| {diff:.3g}, "
        f"prediction agreement {float((pred == pred_ref).mean()):.6f}")

    with phase("bsr kernel"):
        bsr_check(args.seed)


def bsr_check(seed):
    """The bsr MXU path on a small stream clustered enough to tile."""
    from repro.core.dynlp import DynLP
    from repro.data.synth import StreamSpec, gaussian_mixture_stream
    from repro.graph.dynamic import DynamicGraph

    spec = StreamSpec(total_vertices=BSR_ROWS, batch_size=1024, emb_dim=16,
                      class_sep=8.0, noise=0.5, frac_deleted=0.05,
                      seed=seed + 3)
    g_b, g_r = DynamicGraph(emb_dim=16, k=K), DynamicGraph(emb_dim=16, k=K)
    dyn_b, dyn_r = DynLP(g_b, backend="bsr"), DynLP(g_r, backend="ref")
    for b, _ in gaussian_mixture_stream(spec):
        dyn_b.step(b)
        dyn_r.step(b)
    diff = float(np.abs(g_b.f - g_r.f).max())
    check(diff <= BSR_ATOL, f"bsr labels within {BSR_ATOL} of ref on "
                            f"{g_b.num_nodes} rows (max diff {diff:.3g})")


def four_chips(args):
    from repro.launch.mesh import make_stream_mesh

    with phase("data"):
        windows, n_load = make_stream(args.rows, args.seed)
    say(f"[data] {args.rows} rows x {DIM}-d, {len(windows)} windows")
    mesh = make_stream_mesh(4)
    g4, e4 = serve(windows, n_load, mesh=mesh, reads=False)
    check(e4.ingestor.store.n_shards == 4,
          "embedding store row-sharded over 4 chips")
    g1, _ = serve(windows, n_load, reads=False)
    check(fingerprint(g4) == fingerprint(g1),
          "graph bit-identical between the 4-chip and 1-chip engines")
    check(np.array_equal(g4.f, g1.f),
          "labels bit-identical between the 4-chip and 1-chip engines")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help=f"rows to load (the deployment has {ROWS}; the "
                         f"width stays {DIM})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devs[0].platform}); refusing "
              "to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 2
    from repro.launch.platform import enable_compile_cache

    say(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
        f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    (four_chips if args.chips == 4 else one_chip)(args)
    say(f"[timings] {json.dumps({k: round(v, 3) for k, v in TIMINGS.items()})}"
        " (smoke timings, not metrics)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
