"""Mean milliseconds a mutation waited for the service's write lock
(program span ``lp.mutate.lock``, from ``mutate``'s entry to holding the lock)."""

from _program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lp.mutate.lock")
