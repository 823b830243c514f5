"""Mean device milliseconds per fused read gather in the traced window."""

from _common import gather_programs, module_ms


def read(ctx):
    return module_ms(ctx, gather_programs(ctx))
