"""Shared helpers of the per-layer metric readers (not a metric).

A device program shows in the trace as ``jit_<function>(<hash>)``.  Each
reader takes its function names from the program's own objects: the
solve's from the registry entry of every backend that solved a commit of
the window, the gather's and argkmin's from their jitted functions.  A
renamed function fails here, loudly, and a declared metric that finds no
execution fails the traced run (``run.py``), so neither reads as zero.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402


def program_names(*fns) -> set[str]:
    return {"jit_" + f.__name__ for f in fns}


def solve_programs(ctx) -> set[str]:
    import repro.core.stream  # noqa: F401 -- importing ops first meets the program's import cycle
    from repro.kernels import ops

    backends = {st.backend for st in ctx["commit_stats"]} - {"none"}
    return program_names(*(entry() for b in sorted(backends)
                           for entry in ops.backend_spec(b).cache_entry_points))


def gather_programs(ctx) -> set[str]:
    from repro.core import snapshot

    return program_names(snapshot._device_query)


def argkmin_programs(ctx) -> set[str]:
    from repro.kernels import argkmin

    return program_names(argkmin._argkmin_pallas, argkmin._argkmin_xla)


def mean_ms(xs):
    return 1e3 * sum(xs) / len(xs) if xs else None


def module_ms(ctx, names):
    tr = ctx["trace"]
    return None if tr is None else mean_ms(trace_reduce.module_times(tr, names))


def span_ms(ctx, name):
    tr = ctx["trace"]
    return None if tr is None else mean_ms(trace_reduce.span_times(tr, name))
