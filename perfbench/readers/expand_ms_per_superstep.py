"""Host expansion time per superstep: the client's `expand` phase spans
in the stretch (device-fenced in traced runs) over its supersteps
(program spans and counters)."""


def read(ctx):
    steps, t = ctx.supersteps(), ctx.span_seconds("expand")
    if steps <= 0 or t <= 0:
        return None
    return 1e3 * t / steps
