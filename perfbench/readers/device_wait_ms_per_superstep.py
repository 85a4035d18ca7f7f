"""Time the host waits on the device in a fused dispatch, per superstep:
the client's `fused-run` spans in the stretch (from the program's enqueue
until its escape scalars are read) over its supersteps
(`service_supersteps_total`) (program spans and counters)."""


def read(ctx):
    steps, t = ctx.supersteps(), ctx.span_seconds("fused-run")
    if steps <= 0 or t <= 0:
        return None
    return 1e3 * t / steps
