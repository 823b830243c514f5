"""Mean milliseconds from the drain that published a commit's view to the
resolution of its tickets in ``LPService._resolve`` (program interval ``lp.ack.lag``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lp.ack.lag")
