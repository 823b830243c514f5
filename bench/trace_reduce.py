"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced window is the host span ``bench.window`` that the harness
opens around the steady seconds it traces.  Within it:

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each device plane), averaged over the
  devices; idle = window - busy;
* per-module device time: events of the ``XLA Modules`` line, keyed by
  the compiled program's name (``jit_<function>(<hash>)``, a jitted
  function of the program); a program's mean time per execution counts
  only executions wholly inside the window;
* idle gaps: each stretch of the window with no device op, named by the
  innermost benchmark host span open at its midpoint ("host: none" when
  no span was open).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]  # ns
    ops: dict[str, list[tuple[int, int, str]]]  # device -> [(start, end, name)]
    modules: dict[str, list[tuple[int, int, str]]]
    spans: list[tuple[int, int, str]]  # host spans of interest

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_names) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    names = set(span_names) | {WINDOW_SPAN}
    ops, modules, spans = {}, {}, []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dst = ops if line.name == OPS_LINE else modules
                dst.setdefault(plane.name, []).extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        iv = (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                        if e.name == WINDOW_SPAN:
                            window = iv[:2]
                        else:
                            spans.append(iv)
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    return Trace(window=window, ops=ops, modules=modules, spans=spans)


def _clip(iv, window):
    lo, hi = max(iv[0], window[0]), min(iv[1], window[1])
    return (lo, hi) if hi > lo else None


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(x) for x in out]


def busy(tr: Trace) -> dict[str, list[tuple[int, int]]]:
    return {dev: union(c for c in (_clip(e, tr.window) for e in evs) if c)
            for dev, evs in tr.ops.items()}


def busy_s(tr: Trace) -> float:
    per = [sum(hi - lo for lo, hi in ivs) for ivs in busy(tr).values()]
    return (sum(per) / len(per)) * 1e-9 if per else 0.0


def idle_gaps(tr: Trace) -> list[tuple[int, int]]:
    """Idle stretches of the window on the first device."""
    b = busy(tr)
    if not b:
        return [tr.window]
    ivs = b[sorted(b)[0]]
    gaps, at = [], tr.window[0]
    for lo, hi in ivs:
        if lo > at:
            gaps.append((at, lo))
        at = max(at, hi)
    if at < tr.window[1]:
        gaps.append((at, tr.window[1]))
    return gaps


def gap_owner(tr: Trace, gap) -> str:
    mid = (gap[0] + gap[1]) // 2
    best = None
    for lo, hi, name in tr.spans:
        if lo <= mid < hi and (best is None or hi - lo < best[1] - best[0]):
            best = (lo, hi, name)
    return f"host: {best[2]}" if best else "host: none"


def idle_by_span(tr: Trace) -> list[tuple[str, float]]:
    acc = collections.Counter()
    for g in idle_gaps(tr):
        acc[gap_owner(tr, g)] += (g[1] - g[0]) * 1e-9
    return sorted(acc.items(), key=lambda x: -x[1])


def program_of(event_name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``: the program's name without its hash."""
    return event_name.split("(", 1)[0]


def module_times(tr: Trace, names) -> list[float]:
    """Seconds of each device execution of the programs ``names`` that
    lies wholly within the window (first device); an execution cut by the
    window's edge is left out, not counted at its partial length."""
    if not tr.modules:
        return []
    names = set(names)
    lo, hi = tr.window
    return [(e[1] - e[0]) * 1e-9 for e in tr.modules[sorted(tr.modules)[0]]
            if program_of(e[2]) in names and lo <= e[0] and e[1] <= hi]


def top_modules(tr: Trace, n: int = 10) -> list[tuple[str, float]]:
    if not tr.modules:
        return []
    acc = collections.Counter()
    for e in tr.modules[sorted(tr.modules)[0]]:
        c = _clip(e, tr.window)
        if c:
            acc[e[2]] += (c[1] - c[0]) * 1e-9
    return sorted(acc.items(), key=lambda x: -x[1])[:n]


def span_times(tr: Trace, name: str) -> list[float]:
    return [(hi - lo) * 1e-9 for lo, hi, nm in tr.spans
            if nm == name and lo >= tr.window[0] and hi <= tr.window[1]]
