"""Host time of one move commit: the client's `move-commit` spans in the
stretch (snapshot of the slot, re-root, write-back; device-fenced in
traced runs) over the moves it committed (`service_moves_committed_total`)
(program spans and counters).  A program without that counter finds
nothing to read."""


def read(ctx):
    moves, t = (ctx.counter("service_moves_committed_total"),
                ctx.span_seconds("move-commit"))
    if moves <= 0 or t <= 0:
        return None
    return 1e3 * t / moves
