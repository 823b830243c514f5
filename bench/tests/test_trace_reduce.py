"""Trace reduction on a small recorded trace and on synthetic stamps."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import stats
import trace_reduce as tr
from conftest import BENCH

sys.path.insert(0, str(BENCH / "metrics"))
import _common  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "trace_small.xplane.pb"


def synthetic():
    # window 0..100 ns; device busy 10-30 and 25-40 (overlap) and 70-80
    ops = {"/device:TPU:0": [(10, 30, "a"), (25, 40, "b"), (70, 80, "c"), (95, 120, "d")]}
    mods = {"/device:TPU:0": [(10, 40, "jit__ref_donating(12)"), (70, 80, "jit__device_query(3)"),
                              (95, 120, "jit__ref_donating(12)")]}
    spans = [(0, 60, "StreamEngine.submit"), (5, 45, "DynamicGraph.apply_batch"),
             (60, 100, "LPService.pump")]
    return tr.Trace(window=(0, 100), ops=ops, modules=mods, spans=spans)


def test_busy_idle_and_gaps():
    t = synthetic()
    assert tr.union([(10, 30), (25, 40), (70, 80)]) == [(10, 40), (70, 80)]
    assert t.window_s == pytest.approx(100e-9)
    assert tr.busy_s(t) == pytest.approx((30 + 10 + 5) * 1e-9)
    assert tr.idle_gaps(t) == [(0, 10), (40, 70), (80, 95)]
    # gap 0-10 (mid 5): apply_batch is the innermost open span
    assert tr.gap_owner(t, (0, 10)) == "host: DynamicGraph.apply_batch"
    assert dict(tr.idle_by_span(t)) == pytest.approx(
        {"host: DynamicGraph.apply_batch": 10e-9, "host: StreamEngine.submit": 30e-9,
         "host: LPService.pump": 15e-9})
    # the execution cut by the window's end is left out of the per-run times
    assert tr.module_times(t, {"jit__ref_donating"}) == pytest.approx([30e-9])
    assert tr.module_times(t, {"jit__ref"}) == []
    assert tr.span_times(t, "StreamEngine.submit") == pytest.approx([60e-9])
    assert tr.top_modules(t)[0] == ("jit__ref_donating(12)", pytest.approx(35e-9))


@pytest.mark.skipif(not RECORDED.exists(), reason="recorded trace not present")
def test_recorded_trace():
    t = tr.load(str(RECORDED), ["StreamEngine.submit"])
    assert t.ops and t.modules
    assert 0 < tr.busy_s(t) < t.window_s
    # the readers' program names, taken from the program, match the chip's trace
    stats_ref = [SimpleNamespace(backend="ref")]
    assert tr.module_times(t, _common.argkmin_programs(None))
    assert tr.module_times(t, _common.solve_programs({"commit_stats": stats_ref}))
    assert tr.module_times(t, _common.gather_programs(None))
    assert tr.span_times(t, "StreamEngine.submit")
    assert sum(s for _, s in tr.idle_by_span(t)) == pytest.approx(
        t.window_s - tr.busy_s(t), rel=1e-6)


def _sent(sched, sent, committed=None, commit=None, ops=16, done=None):
    tk = SimpleNamespace(committed_at=committed, commit_id=commit, completed_at=done,
                         error=None)
    return SimpleNamespace(sched=sched, sent=sent, ticket=tk,
                           write=SimpleNamespace(ops=ops))


def test_window_alignment_and_percentiles():
    writes = [_sent(0.5, 0.5, 1.0, 1),  # before the window: not attempted
              _sent(1.2, 1.3, 2.0, 2), _sent(1.5, 1.5, 3.0, 3),
              _sent(2.9, 3.0, None, None),  # never committed: failed
              _sent(3.5, 3.5, 4.0, 4)]  # after the window
    reads = [_sent(1.1, 1.1, done=1.15), _sent(2.0, 2.2, done=2.4), _sent(4.0, 4.0, done=4.1)]
    w = stats.window_numbers(writes, reads, t_open=1.0, t_close=3.0, c_open=1, c_close=3)
    assert w["attempted"] == 5 and w["failed"] == 1
    assert w["ops_committed"] == 32  # commits 2 and 3
    assert w["write_lat"] == pytest.approx([800.0, 1500.0])
    assert w["read_lat"] == pytest.approx([50.0, 400.0])
    assert w["write_late"] == pytest.approx([100.0, 0.0, 100.0])
    e = stats.end_to_end(w, 12.0)
    assert e["write_ops_per_s"] == pytest.approx(16.0)
    assert e["freshness_p95_ms"] == pytest.approx(np.percentile([800.0, 1500.0], 95))
    assert e["read_p99_ms"] == pytest.approx(np.percentile([50.0, 400.0], 99))
    assert e["freshness_p50_ms"] == pytest.approx(1150.0)
    assert e["read_p90_ms"] == pytest.approx(np.percentile([50.0, 400.0], 90))
    assert stats.quartile_spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(3.0 / 3.0)
