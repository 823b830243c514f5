"""Mean milliseconds an admission window stayed open, from its first
queued mutation to ``LPService._admit`` (program interval ``lp.window.wait``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lp.window.wait")
