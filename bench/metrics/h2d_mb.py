"""Megabytes (10**6 bytes) of host arrays handed to the device per submitted
window (program counter ``engine.h2d_bytes`` per ``engine.submit`` span)."""

from _program import counter_per_span


def read(ctx):
    b = counter_per_span(ctx, "engine.h2d_bytes", "engine.submit")
    return None if b is None else b / 1e6
