#!/usr/bin/env python3
"""The control: the reference in the program's place, one precision down.

    python3 bench/control.py --workload arxiv.churn-read --seeds 5 6 7

The configurations state float32, so the control is the plain reference
computed through bfloat16: similarities and canonical weights rounded to
bfloat16 while it replays the cell's own windows from the preload, and
its labels a Jacobi solve carried in bfloat16.  Its outputs go through
the same comparison as the program's (``check.py``'s numbers), against
the float32 reference.  A sound limit lies above what the program reads
and below what the control reads.  The benchmark's own runs never run
this; it exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import cells  # noqa: E402
import lp  # noqa: E402


def windows_of(mix: dict, stream, n_windows: int) -> list[list]:
    """The mix's write sequence cut into full admission windows."""
    per = int(mix["service"]["window_ops"])
    out = []
    for _ in range(n_windows):
        ws, ops = [], 0
        while ops < per:
            w = stream.next()
            ws.append(w)
            ops += w.ops
        out.append(ws)
    return out


def readings(cell: dict, seed: int, n_windows: int) -> dict[str, float]:
    import jax.numpy as jnp

    import check
    import preload
    import traffic
    from reference import RefGraph

    cfg, mix = cell["config"], cell["mix"]
    words = preload.seed_words(seed, 6)
    pool = int(mix["insert_pool_rows"])
    data = preload.make_data(cfg, seed, pool)
    state = preload.state_arrays(cfg, data)
    stream = traffic.WriteStream(mix, data.cls, data.labels0, data.n0, pool, int(words[2]))
    wins = windows_of(mix, stream, n_windows)
    k = int(cfg["k"])
    ref = RefGraph(state, k, capacity=len(data.emb))
    ctl = RefGraph(state, k, capacity=len(data.emb), dtype=jnp.bfloat16, precision="default")
    for ws in wins:
        sents = [traffic.Sent(0.0, 0.0, write=w) for w in ws]
        args = check.batch_args(sents, data.emb)
        ref.apply(**args)
        ctl.apply(**args)
    out = {"graph_rows_differing": float(check._rows_differing(
        ctl.knn_idx, ctl.knn_wgt, ref.knn_idx, ref.knn_wgt)),
        "edges_differing": float(check._entries_differing(ctl.edges(), ref.edges()))}
    # the control's labels: its own problem solved in bfloat16 from the preload's f
    p_ctl = ctl.problem()
    f = np.full(ctl.num_nodes, 0.5, np.float32)
    f[: data.n0] = state["f"]
    f = np.where(ctl.labels == 1, 1.0, np.where(ctl.labels == 0, 0.0, f)).astype(np.float32)
    fu, _ = preload.solve(p_ctl, f[p_ctl.unl_ids], 0.0, max_iters=2000, dtype=jnp.bfloat16)
    f[p_ctl.unl_ids] = fu
    p = ref.problem()
    r = lp.residuals(p, f[p.unl_ids])
    out["label_residual_max"] = float(r.max())
    out["label_residual_mean"] = float(r.mean())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--windows", type=int, default=8)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cell = cells.cell(args.workload)
    for s in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": s,
                          "control": readings(cell, s, args.windows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
