"""Share of the network forward's device time that the chip needs at
least for its work: max(ops / peak op rate, bytes / peak bandwidth)
over the summed durations of the forward program on the trace's "XLA
Modules" line.  The work is counted by perfbench/net_work.py at
`max_batch` rows (the simulation server pads every microbatch to it)
for each microbatch dispatched in the stretch
(`sim_server_batches_total`).

The peak op rate is the table's bfloat16 rate, while the network runs
in float32 at matmul precision `highest`, which the chip computes in
several bfloat16 passes; the forward is bound by bytes all the same.
The program is matched by the name its jitted function gives it,
`jit_policy_value_forward`; a program whose forward has another name
finds nothing to read."""

import re

from perfbench import net_work, work

MODULE = re.compile(r"^jit_policy_value_forward(\(|$)")


def read(ctx):
    events = [e for e in ctx.module_events() if MODULE.match(e[0])]
    batches = ctx.counter("sim_server_batches_total")
    if not events or batches <= 0 or ctx.peaks is None:
        return None
    t = sum(e[2] - e[1] for e in events) * 1e-9
    one = net_work.per_microbatch(ctx.config["net"],
                                  ctx.config["sim"]["max_batch"])
    need, bound = work.least_seconds(
        {"ops": one["ops"] * batches, "bytes": one["bytes"] * batches},
        ctx.peaks)
    ctx.log(f"net roofline: {len(events)} forwards on the trace, "
            f"{batches:.0f} microbatches counted, {one['bytes']} bytes and "
            f"{one['ops']} ops each; bound by {bound}; least {need!r}s of "
            f"{t!r}s forward time")
    return 100.0 * need / t
