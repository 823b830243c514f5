"""Shared helpers of the readers of the program's own recorder (not a metric).

The program keeps running totals of its spans, intervals and counters
(``repro.core.trace``; ``ServiceStats.spans`` as ``{name: (count, total
ms)}`` and ``ServiceStats.counters``).  ``run.py`` takes ``ServiceStats``
at tracer start and stop (``ctx["service_traced"]``); a reader divides the
change of a total between the two by the change of a count.  A program
that keeps no recorder (no ``spans`` field) gives nothing to read.  A name
the recorder does not hold raises, so a rename never reads as zero; a
count that did not move gives None, and ``run.py`` fails the traced run.
"""


def _stats(ctx):
    traced = ctx.get("service_traced")
    if traced is None or not hasattr(traced[1], "spans"):
        return None
    return traced


def _count(before, after, span):
    return after.spans[span][0] - before.spans.get(span, (0, 0.0))[0]


def span_mean_ms(ctx, name):
    """Mean milliseconds per span or interval ``name`` over the traced seconds."""
    traced = _stats(ctx)
    if traced is None:
        return None
    before, after = traced
    n = _count(before, after, name)
    if n <= 0:
        return None
    return (after.spans[name][1] - before.spans.get(name, (0, 0.0))[1]) / n


def counter_per_span(ctx, counter, span):
    """Change of ``counter`` per ``span`` over the traced seconds."""
    traced = _stats(ctx)
    if traced is None:
        return None
    before, after = traced
    n = _count(before, after, span)
    if n <= 0:
        return None
    return (after.counters[counter] - before.counters.get(counter, 0)) / n
