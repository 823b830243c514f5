"""Mean milliseconds per window of ``build_host_problem`` and the frontier in
``StreamEngine.submit`` (program span ``engine.submit.build``)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "engine.submit.build")
