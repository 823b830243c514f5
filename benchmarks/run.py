"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig7]

Emits ``name,us_per_call,derived`` CSV lines per benchmark (us_per_call is
total wall μs of the benchmark's DynLP runs; derived carries the headline
claim metric), after each benchmark's own detail lines.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from benchmarks import (
    fig5_scaling,
    fig6_delta,
    fig7_itlp,
    fig8_stlp,
    stream_throughput,
    table3_exec,
    table4_batch,
)

BENCHES = {
    "fig5": (fig5_scaling.main, "iterations/time grow with dataset size"),
    "fig6": (fig6_delta.main, "delta controls iterations & accuracy"),
    "fig7": (fig7_itlp.main, "DynLP beats ITLP iterations/speedup"),
    "fig8": (fig8_stlp.main, "DynLP vs STLP + O(U^2) memory wall"),
    "table3": (table3_exec.main, "execution time across datasets"),
    "table4": (table4_batch.main, "method matrix at batch sizes"),
    "stream": (stream_throughput.main,
               "compile-once engine >=3x naive rebuild per batch"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default=None,
                    choices=("ref", "ell_pallas", "bsr"),
                    help="kernels.ops backend override; off-TPU the Pallas "
                         "backends run in the interpreter (CPU numbers say "
                         "nothing about chip speed)")
    args = ap.parse_args()

    if args.backend:
        # Propagate to every DynLP/StreamEngine built downstream.  Pallas
        # kernels resolve interpret=None from the platform: compiled on a
        # TPU, interpreted on a host without one.
        os.environ["REPRO_BACKEND"] = args.backend
        from repro.kernels import ops
        if args.backend != "ref" and not ops.on_tpu():
            print(f"# no TPU attached: backend={args.backend} runs with "
                  "interpret=True kernels", flush=True)

    failures = 0
    summary = []
    for name, (fn, claim) in BENCHES.items():
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        try:
            fn(full=args.full)
            us = (time.perf_counter() - t0) * 1e6
            summary.append(f"{name},{us:.0f},{claim}")
        except Exception:
            failures += 1
            traceback.print_exc()
            summary.append(f"{name},FAILED,{claim}")
        print(flush=True)
    print("== summary: name,us_per_call,derived ==")
    for line in summary:
        print(line)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
