"""Device time of the in-tree executor per superstep on the classic
phase path: the summed durations of its jitted programs over the arena
(select, insert, finalize and backup) on the trace's "XLA Modules" line,
over the supersteps the stretch ran (`service_supersteps_total`).

A program holds the UCT kernel with everything around it that serves
it: the relayouts of the whole arena into the kernels' packed layout
and back, and the copies that move the kernels' operands into the
chip's fast memory (their layouts name memory space 1) and out.  So
where `uct_kernel_us_per_superstep` times the kernel bodies alone, this
shows what moving the whole arena costs.  The programs are matched by
the names their jitted functions give them today.  A fused dispatch
runs the in-tree phases inside one program of its own, so on that path
this finds nothing to read."""

import re

MODULES = re.compile(r"^jit_(select|insert|finalize|backup)_arena(\(|$)")


def read(ctx):
    events = [e for e in ctx.module_events() if MODULES.match(e[0])]
    steps = ctx.supersteps()
    if not events or steps <= 0:
        return None
    names = sorted({e[0].split("(")[0] for e in events})
    ctx.log(f"in-tree programs: {len(events)} matched, names {names}")
    return 1e-3 * sum(e[2] - e[1] for e in events) / steps
