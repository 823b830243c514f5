"""Mean milliseconds per window of arrival ordering, ``apply_batch`` and the
relabel bookkeeping in ``StreamEngine.submit`` (program span ``engine.submit.apply``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "engine.submit.apply")
