"""Mean milliseconds from the end of the submit that dispatched a solve to its
commit in ``StreamEngine.drain`` (program interval ``engine.inflight``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "engine.inflight")
