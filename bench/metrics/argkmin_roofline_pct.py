"""Share of its roofline that the argkmin pass reaches, in percent.

Work per call from shapes: FLOPs 2 * cap * M * dim_pad (the similarity
matmul); bytes: the store, its valid and threshold rows and the batch
read once, the candidate lists and the displacement row written once.
The least time is the larger of FLOPs over the bf16 peak and bytes over
HBM bandwidth; the share is the summed least time of the calls in the
traced window over their summed device time.
"""

import sys

from _common import argkmin_programs, trace_reduce

LANES = 128  # the kernel's candidate block width


def batch_bucket(m, floor=8):
    b = floor
    while b < m:
        b *= 2
    return b


def work(cap, dp, mp):
    flops = 2.0 * cap * mp * dp
    nbytes = 4.0 * (cap * dp + 2 * cap + mp * dp + mp + 2 * mp * LANES + cap)
    return flops, nbytes


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    times = trace_reduce.module_times(tr, argkmin_programs(ctx))
    lo, hi = ctx["trace_perf"]
    calls = [m for t, m in ctx["selects"] if lo <= t <= hi]
    if not times or not calls:
        return None
    peak = ctx["cell"]["peaks"][ctx["device_kind"]]  # unknown kind: KeyError
    cap, dp = ctx["store"]
    least, bound = 0.0, {"flops": 0, "bytes": 0}
    for m in calls:
        flops, nbytes = work(cap, dp, batch_bucket(m))
        tf, tb = flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
        least += max(tf, tb)
        bound["flops" if tf >= tb else "bytes"] += 1
    # calls and executions pair up in the window; scale by executions seen
    least *= len(times) / len(calls)
    print(f"[argkmin] {len(times)} executions, {len(calls)} calls, bound by "
          f"{max(bound, key=bound.get)} ({bound})", file=sys.stderr)
    return 100.0 * least / sum(times)
