"""Share of the traced window with no operation on the device, in percent."""

from _common import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(tr) / tr.window_s)
