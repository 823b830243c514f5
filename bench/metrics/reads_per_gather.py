"""Read tickets fused into each device gather over the traced seconds
(change of the ServiceStats counters between tracer start and stop)."""


def read(ctx):
    before, after = ctx["service_traced"]
    batches = after.read_batches - before.read_batches
    return (after.read_tickets - before.read_tickets) / batches if batches else None
