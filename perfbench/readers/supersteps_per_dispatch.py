"""Supersteps each fused device dispatch ran: service_supersteps_total
over service_fused_dispatches_total (program counters)."""


def read(ctx):
    d = ctx.counter("service_fused_dispatches_total")
    if d <= 0:
        return None
    return ctx.supersteps() / d
