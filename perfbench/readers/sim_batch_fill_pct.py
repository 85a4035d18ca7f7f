"""How full the simulation server's microbatches ran: the mean of the
`sim_server_batch_fill` histogram (real rows per dispatched microbatch)
in the stretch over the configured max_batch (program counters)."""


def read(ctx):
    n = ctx.counter("sim_server_batch_fill_count")
    if n <= 0:
        return None
    mean = ctx.counter("sim_server_batch_fill_sum") / n
    return 100.0 * mean / ctx.config["sim"]["max_batch"]
