"""Device time of the UCT select and backup kernels per superstep: the
summed durations of their events in the device trace over the
supersteps the stretch ran (`service_supersteps_total`).

The kernels carry no stable name yet, so the events are matched by the
names they have today on a TPU v5e: the Mosaic custom calls of the two
`pallas_call`s are ops named `select_arena.<n>` and `backup_arena.<n>`,
inside the fused program's loop (Pong) or the jitted `select_arena` /
`backup_arena` modules (Gomoku)."""

import re

KERNELS = re.compile(r"^(select|backup)_arena(\.\d+)?$")


def kernel_events(ctx):
    return [e for e in ctx.device_events() if KERNELS.match(e[0])]


def kernel_seconds(ctx):
    """Summed device seconds of the matched kernel events, or None."""
    events = kernel_events(ctx)
    if not events:
        return None
    names = sorted({e[0] for e in events})
    ctx.log(f"uct kernel events: {len(events)} matched, names {names}")
    return sum(e[2] - e[1] for e in events) * 1e-9


def read(ctx):
    t, steps = kernel_seconds(ctx), ctx.supersteps()
    if t is None or steps <= 0:
        return None
    return 1e6 * t / steps
