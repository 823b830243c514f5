"""Mean milliseconds per window of staging the snapshot and frontier into the
device buffers in ``StreamEngine.submit`` (program span ``engine.submit.stage``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "engine.submit.stage")
