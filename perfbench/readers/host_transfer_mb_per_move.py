"""Megabytes copied between host and device per committed move: the
client's `service_host_transfer_bytes_total` over every call site (the
fused dispatch's upload and readback, the commit's snapshot and
write-back) and both directions, over `service_moves_committed_total`
(program counters).  A program without those counters finds nothing to
read."""


def read(ctx):
    moves = ctx.counter("service_moves_committed_total")
    moved = ctx.counter("service_host_transfer_bytes_total")
    if moves <= 0 or moved <= 0:
        return None
    return moved / 1e6 / moves
