"""Host time spent on the network per superstep: the client's
`sim-finalize` spans in the stretch (the host waiting on one
microbatch's forward, then scattering its values and priors to the rows
that asked) over its supersteps (`service_supersteps_total`) (program
spans and counters).  A program without that span finds nothing to
read."""


def read(ctx):
    steps, t = ctx.supersteps(), ctx.span_seconds("sim-finalize")
    if steps <= 0 or t <= 0:
        return None
    return 1e3 * t / steps
