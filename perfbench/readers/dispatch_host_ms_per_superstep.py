"""Host time of the fused dispatch around the device program, per
superstep: the client's `fused-upload` (state image built and copied to
the device), `fused-readback` (sizes and states copied back into the
host tables) and `admit` (a slot reset for a new request) spans in the
stretch, over its supersteps (`service_supersteps_total`) (program spans
and counters).  A program without the upload span finds nothing to read."""

SPANS = ("fused-upload", "fused-readback", "admit")


def read(ctx):
    steps = ctx.supersteps()
    if steps <= 0 or ctx.span_seconds("fused-upload") <= 0:
        return None
    return 1e3 * sum(ctx.span_seconds(n) for n in SPANS) / steps
