"""Mean device milliseconds per propagation solve in the traced window."""

from _common import module_ms, solve_programs


def read(ctx):
    return module_ms(ctx, solve_programs(ctx))
