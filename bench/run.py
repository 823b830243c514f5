#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload arxiv.churn-read --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``) names a deployment (``configs/``) and a
traffic mix (``traffic/``).  Set-up makes every row from the seed,
preloads the engine's state (``preload.py``), starts ``LPService`` with
its background driver over ``StreamEngine(ingest="device")``, builds
every program size the traffic can reach (``warm.py``) and warms up on
the cell's own traffic until nothing compiles; a program built inside
the measured window fails the run.  The measured
window opens at the first commit after warm-up and closes at the first
commit at or after ``--seconds`` later.  Once every request scheduled in
the window is answered, the service stops, the peak device memory is
read, the program's state is freed, and the reference replays the run
(``check.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
a few steady seconds of the window and prints its per-layer metrics
(one reader per metric in ``metrics/``).  The last stdout line is the
JSON result; the numbers compared, each beside its limit, are the last
stderr lines and the result's last key.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import stats  # noqa: E402

NO_CHIP = 2
NOTHING_READ = 3
# where a program built inside the measured window fails the run; off the
# chip only the tests run, at sizes whose rows cross the engine's rungs
COMPILE_FREE_PLATFORMS = ("tpu",)


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Compiles:
    """Programs built or loaded from the compile cache (every jit cache miss)."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), str(kw.get("fun_name", "?"))))

    @property
    def last(self) -> float | None:
        return self.events[-1][0] if self.events else None

    def between(self, lo: float, hi: float) -> list[str]:
        return [name for t, name in self.events if lo <= t < hi]


def device_info(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < chips):
        say(f"bench: needs {chips} TPU chip(s); jax found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return None
    return devs


def wait_commit(hooks, drv, svc, after: float, timeout: float):
    """First commit at or after ``after``: (time, commit id)."""
    t_end = time.perf_counter() + timeout
    while time.perf_counter() < t_end:
        raise_if_failed(drv, svc)
        with hooks.lock:
            for t, c, _ in hooks.commits:
                if t >= after:
                    return t, c
        time.sleep(0.002)
    raise TimeoutError(f"no commit within {timeout} s")


def raise_if_failed(drv, svc):
    if drv.errors:
        raise RuntimeError("traffic driver failed") from drv.errors[0]
    d = svc._driver
    if d is not None and d.error is not None:
        raise RuntimeError("service driver failed") from d.error


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devs) -> dict:
    import jax

    import preload
    import spans
    import traffic
    import warm
    from repro.core.stream import StreamEngine
    from repro.graph.dynamic import DynamicGraph
    from repro.serving.lp_service import LPService

    cfg, mix = cell["config"], cell["mix"]
    compiles = Compiles()
    words = preload.seed_words(seed, 6)
    pool = int(mix["insert_pool_rows"])
    data = preload.make_data(cfg, seed, pool)
    state = preload.state_arrays(cfg, data)
    g = DynamicGraph(emb_dim=int(cfg["emb_dim"]), k=int(cfg["k"]))
    g.load_state_arrays(state)
    eng = StreamEngine(g, delta=float(cfg["delta"]), ingest="device")
    has_reads = float(mix.get("read_requests_per_s", 0)) > 0
    hooks = spans.Hooks(keep_views=has_reads).install()
    hooks.views[0] = eng.committed_view()
    svc = LPService(eng, **mix["service"])
    stream = traffic.WriteStream(mix, data.cls, data.labels0, data.n0, pool, int(words[2]))
    bursts = [[stream.next(kind) for _ in range(n)]
              for kind, sizes in mix.get("warmup_bursts", {}).items() for n in sizes]
    horizon = float(mix["warmup_max_s"]) + seconds + 30.0
    plan = traffic.make_plan(mix, stream, horizon, int(words[3]))
    drv = traffic.Driver(svc, plan, data.emb, mix)
    t_preload = time.perf_counter()
    say(f"[setup] data + preload {t_preload - T_START:.3f} s: {data.n0} rows, "
        f"{int((state['labels'] >= 0).sum())} labelled, {len(state['src']) // 2} edges")

    tracer = None
    try:
        t_w = time.perf_counter()
        warm.supernode(mix)
        warm.kth_rungs(g, eng.ingestor)
        say(f"[setup] warmed the supernode sizes and k-th rungs in "
            f"{time.perf_counter() - t_w:.3f} s")
        svc.start()
        t_w = time.perf_counter()
        warm.bursts(svc, drv, bursts, data.emb)
        say(f"[setup] warmed the bursts in {time.perf_counter() - t_w:.3f} s")
        t_w = time.perf_counter()
        warm.reads(svc, mix)
        say(f"[setup] warmed the read sizes in {time.perf_counter() - t_w:.3f} s, "
            f"{len(compiles.events)} programs built so far")
        t0 = time.perf_counter()
        drv.start(t0)
        # warm-up on the cell's own traffic until nothing compiles
        while True:
            time.sleep(0.05)
            raise_if_failed(drv, svc)
            now = time.perf_counter()
            el = now - t0
            quiet = compiles.last is None or now - compiles.last >= float(mix["warmup_quiet_s"])
            if el >= float(mix["warmup_max_s"]) or (
                    el >= float(mix["warmup_s"]) and quiet
                    and len(hooks.commits) >= int(mix["warmup_commits"])):
                break
        t_open, c_open = wait_commit(hooks, drv, svc, time.perf_counter(), 600)
        setup_s = t_open - T_START
        say(f"[setup] warm-up {t_open - t0:.3f} s, {len(hooks.commits)} commits; "
            f"window opens at commit {c_open}")
        if trace:
            tracer = Tracer().start()
            svc_traced = [svc.stats()]
            wait_commit(hooks, drv, svc, t_open + float(mix["trace_s"]), 600)
            tracer.stop()
            svc_traced.append(svc.stats())
        t_close, c_close = wait_commit(hooks, drv, svc, t_open + seconds, 600)
        in_window = compiles.between(t_open, t_close)
        drv.finish(t_close)
        raise_if_failed(drv, svc)
        svc.sync()
        t_wait = time.perf_counter() + float(mix["drain_timeout_s"])
        for s in drv.reads:
            try:
                s.ticket.wait(max(0.0, t_wait - time.perf_counter()))
            except Exception:  # noqa: BLE001 — counted as failed below
                pass
        svc.close()
    finally:
        drv.halt()
        if tracer is not None:
            tracer.stop()
        if svc.driver_running:
            svc.stop()
    sstats = svc.stats()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devs[:cell["chips"]]) \
        if devs[0].platform == "tpu" else 0
    views = dict(hooks.views)
    final = eng.committed_view()
    views[final.commit_id] = final
    commit_t = {c: t for t, c, _ in hooks.commits}
    window_commits = [c for c in commit_t if c_open < c <= c_close]
    rec = {"writes": drv.writes, "reads": drv.reads, "views": views,
           "window_commits": window_commits,
           "graph": {a: np.array(getattr(g, a)) for a in
                     ("knn_idx", "knn_wgt", "src", "dst", "wgt")}}
    w = stats.window_numbers(drv.writes, drv.reads, t_open, t_close, c_open, c_close)
    commit_stats = [st for t, c, st in hooks.commits if c_open < c <= c_close]
    traced = None
    if tracer is not None:
        import trace_reduce
        traced = trace_reduce.load(trace_reduce.find_xplane(tracer.dir),
                                   [spans.span_name(c, n) for _, c, n in spans.SPANS])
        shutil.rmtree(tracer.dir, ignore_errors=True)
    ctx = {"cell": cell, "trace": traced, "commit_stats": commit_stats,
           "service": sstats, "store": (eng.ingestor.store.capacity, eng.ingestor.store.dp),
           "window": w, "selects": list(hooks.selects), "device_kind": devs[0].device_kind,
           "trace_perf": (tracer.t_start, tracer.t_stop) if tracer else None,
           "service_traced": svc_traced if tracer else None}
    say(f"[window] {t_close - t_open:.3f} s, commits {c_open}..{c_close}, "
        f"{w['ops_committed']} ops committed, {len(w['write_lat'])} writes and "
        f"{len(w['read_lat'])} reads scheduled, rows {g.num_nodes} (alive {g.num_alive}), "
        f"compiles in window {len(in_window)}, peak device bytes {peak}")
    say(f"[window] generator lateness writes p50/max "
        f"{stats.pct(w['write_late'], 50)}/{stats.pct(w['write_late'], 100)} ms, reads p50/max "
        f"{stats.pct(w['read_late'], 50)}/{stats.pct(w['read_late'], 100)} ms; freshness p50 "
        f"{stats.pct(w['write_lat'], 50)} ms")
    if in_window and devs[0].platform in COMPILE_FREE_PLATFORMS:
        raise RuntimeError(f"{len(in_window)} programs compiled or loaded inside the measured "
                           f"window: {sorted(set(in_window))}; warm-up must build them")
    hooks.uninstall()
    del svc, eng, g, drv, hooks
    gc.collect()
    return {"rec": rec, "window": w, "ctx": ctx, "setup_s": setup_s, "peak": peak,
            "state": state, "emb": data.emb, "words": words, "trace": traced}


class Tracer:
    """The profiler over a steady part of the window, with its span."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.ann = None

    def start(self) -> "Tracer":
        import jax

        import trace_reduce
        jax.profiler.start_trace(self.dir)
        self.t_start = time.perf_counter()
        self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self.ann.__enter__()
        return self

    def stop(self) -> None:
        import jax

        if self.ann is not None:
            self.t_stop = time.perf_counter()
            self.ann.__exit__(None, None, None)
            self.ann = None
            jax.profiler.stop_trace()


def main(argv=None, *, allow_cpu: bool = False, overrides: dict | None = None,
         root: Path = cells.ROOT) -> int:
    args = parse(argv)
    cell = cells.cell(args.workload, root)
    for key in ("config", "mix"):
        cell[key] = {**cell[key], **(overrides or {}).get(key, {})}
    devs = device_info(cell["chips"], allow_cpu)
    if devs is None:
        return NO_CHIP
    import jax

    from repro.launch.platform import enable_compile_cache
    # the cache lives inside the checkout whatever the environment names:
    # only the first run of a cell in a checkout compiles, and two
    # checkouts share nothing
    say(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache(env={})}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)

    import check
    t_ref = time.perf_counter()
    nums = check.compare(cell["config"], out["state"], out["emb"], out["rec"],
                         int(out["words"][4]))
    say(f"[check] reference took {time.perf_counter() - t_ref:.3f} s")
    for n, v in nums.items():
        say(f"[measured] {n} = {v!r}")
    limits = cell["config"]["limits"]
    compared = {n: [nums[n], limits[n]] for n in limits if n in nums}
    correct = all(v <= lim for v, lim in compared.values())
    w = out["window"]
    result = {"correct": bool(correct), "attempted": w["attempted"], "failed": w["failed"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(out["peak"])}
    if args.trace:
        import trace_reduce
        tr = out["trace"]
        metrics = {}
        for m in cell["per_layer"]:
            v = cells.reader(m["name"])(out["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        # a declared metric that finds nothing to read fails the run, loudly
        # (off the chip the trace has no device plane to read)
        silent = [m["name"] for m in cell["per_layer"] if m["name"] not in metrics
                  and (devs[0].platform == "tpu" or m["source"] != "device_trace")]
        if silent:
            say(f"bench: declared metrics read nothing in this cell: {silent}")
            return NOTHING_READ
        dev["busy_s"] = trace_reduce.busy_s(tr)
        dev["window_s"] = tr.window_s
        result["metrics"] = metrics
        result["device"] = dev
        result["breakdown"] = {"device_ops": [list(x) for x in trace_reduce.top_modules(tr)],
                               "idle_gaps": [list(x) for x in trace_reduce.idle_by_span(tr)[:10]]}
    else:
        values = stats.end_to_end(w, out["setup_s"])
        say("[window] " + ", ".join(f"{n} {v}" for n, v in values.items()))
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        result["device"] = dev
    result["compared"] = {n: {"value": v, "limit": lim} for n, (v, lim) in compared.items()}
    for n, (v, lim) in compared.items():
        say(f"[compared] {n} = {v!r} (limit {lim!r}) {'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")
    sys.exit(main())
