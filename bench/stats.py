"""Window alignment and the end-to-end arithmetic, on host-clock stamps.

The window opens at a commit (``t_open``) and closes at the first commit
at or after ``--seconds`` later (``t_close``), so a few slow commits
cannot quantise the rate.  A request belongs to the window when it was
scheduled inside it (a closed-loop write: when it was sent).
"""

from __future__ import annotations

import numpy as np


def pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def window_numbers(writes, reads, t_open, t_close, c_open, c_close) -> dict:
    win_w = [s for s in writes if t_open <= s.sched <= t_close]
    win_r = [s for s in reads if t_open <= s.sched <= t_close]
    done_w = [s for s in win_w if s.ticket.committed_at is not None]
    done_r = [s for s in win_r if s.ticket.completed_at is not None and s.ticket.error is None]
    ops = sum(s.write.ops for s in writes
              if s.ticket.commit_id is not None and c_open < s.ticket.commit_id <= c_close)
    return {
        "t_open": t_open, "t_close": t_close, "c_open": c_open, "c_close": c_close,
        "ops_committed": ops,
        "write_lat": [(s.ticket.committed_at - s.sched) * 1e3 for s in done_w],
        "read_lat": [(s.ticket.completed_at - s.sched) * 1e3 for s in done_r],
        "write_late": [(s.sent - s.sched) * 1e3 for s in win_w],
        "read_late": [(s.sent - s.sched) * 1e3 for s in win_r],
        "attempted": len(win_w) + len(win_r),
        "failed": (len(win_w) - len(done_w)) + (len(win_r) - len(done_r)),
    }


def end_to_end(w: dict, setup_s: float) -> dict:
    return {
        "write_ops_per_s": w["ops_committed"] / (w["t_close"] - w["t_open"]),
        "freshness_p50_ms": pct(w["write_lat"], 50),
        "freshness_p95_ms": pct(w["write_lat"], 95),
        "read_p50_ms": pct(w["read_lat"], 50),
        "read_p90_ms": pct(w["read_lat"], 90),
        "read_p99_ms": pct(w["read_lat"], 99),
        "setup_s": setup_s,
    }


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles(n=4)``."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
