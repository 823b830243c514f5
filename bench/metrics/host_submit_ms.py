"""Mean host milliseconds per StreamEngine.submit span in the traced window."""

from _common import span_ms


def read(ctx):
    return span_ms(ctx, "StreamEngine.submit")
