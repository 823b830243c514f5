#!/usr/bin/env python3
"""Find the highest write rate an open-loop mix sustains (its knee).

    python3 bench/sweep.py --workload arxiv.churn-read --seed 3 --rates 1000 2000 4000 --step 20

One process preloads the cell once, starts the service, then offers the
mix at each rate in turn for ``--step`` seconds, reads included, and
prints per step the offered and committed write rates, freshness in the
first and second half of the step, and how late the generator ran at
the end.  Every request of a step is sent, however late, and committed
before the next step starts.  A rate is sustained when the committed rate keeps up with the
offered one and neither freshness nor lateness grows over the step.
The cell then offers about four fifths of the knee (its mix file).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--step", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    import preload
    import traffic
    from repro.core.stream import StreamEngine
    from repro.graph.dynamic import DynamicGraph
    from repro.launch.platform import enable_compile_cache
    from repro.serving.lp_service import LPService

    enable_compile_cache()
    cell = cells.cell(args.workload)
    cfg, mix = cell["config"], cell["mix"]
    words = preload.seed_words(args.seed, 6)
    pool = int(mix["insert_pool_rows"])
    data = preload.make_data(cfg, args.seed, pool)
    state = preload.state_arrays(cfg, data)
    g = DynamicGraph(emb_dim=int(cfg["emb_dim"]), k=int(cfg["k"]))
    g.load_state_arrays(state)
    svc = LPService(StreamEngine(g, delta=float(cfg["delta"]), ingest="device"),
                    **mix["service"])
    stream = traffic.WriteStream(mix, data.cls, data.labels0, data.n0, pool, int(words[2]))
    svc.start()
    try:
        for i, rate in enumerate([args.rates[0]] + list(args.rates)):
            step_mix = {**mix, "write_ops_per_s": rate}
            plan = traffic.make_plan(step_mix, stream, args.step, int(words[3]) + i)
            drv = traffic.Driver(svc, plan, data.emb, step_mix)
            t0 = time.perf_counter()
            drv.start(t0)
            # every request of the step is sent, however late: a request the
            # stream made but never sent would leave later ids past the last row
            drv.finish(t0 + args.step)
            if drv.errors:
                raise RuntimeError("traffic driver failed") from drv.errors[0]
            t1 = t0 + args.step
            svc.sync()
            mid = t0 + args.step / 2
            w = [s for s in drv.writes if s.ticket.committed_at is not None]
            done = sum(s.write.ops for s in w if s.ticket.committed_at <= t1)
            first = [(s.ticket.committed_at - s.sched) * 1e3 for s in w if s.sched < mid]
            second = [(s.ticket.committed_at - s.sched) * 1e3 for s in w if s.sched >= mid]
            late = [(s.sent - s.sched) * 1e3 for s in drv.writes]
            rl = [(s.ticket.completed_at - s.sched) * 1e3 for s in drv.reads
                  if s.ticket.completed_at is not None]
            print(json.dumps({
                "step": i, "warm-up": i == 0, "offered_ops_per_s": rate,
                "sent_ops_per_s": sum(s.write.ops for s in drv.writes) / args.step,
                "committed_ops_per_s": done / args.step,
                "freshness_p50_ms": [stats.pct(first, 50), stats.pct(second, 50)],
                "freshness_p95_ms": [stats.pct(first, 95), stats.pct(second, 95)],
                "lateness_end_ms": float(np.mean(late[-20:])) if late else None,
                "read_p99_ms": stats.pct(rl, 99), "rows": g.num_nodes}), flush=True)
    finally:
        d = svc._driver
        if d is not None and d.error is not None:
            import traceback
            traceback.print_exception(d.error)
        svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
