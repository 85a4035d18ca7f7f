"""Share of the UCT kernels' device time that the chip needs at least
for the in-tree work of the stretch: max(ops / peak op rate, bytes /
peak bandwidth) over the kernel time, with the work counted from the
shapes by perfbench/work.py (an upper bound), for every superstep of
every active slot."""

from perfbench import spec, work

kernels = spec.load_module(spec.reader_path("uct_kernel_us_per_superstep"),
                           "reader")


def read(ctx):
    t = kernels.kernel_seconds(ctx)
    steps = ctx.supersteps()
    if t is None or steps <= 0 or ctx.peaks is None:
        return None
    one = work.per_slot_superstep(**ctx.shapes)
    n = steps * ctx.active_slots
    need, bound = work.least_seconds(
        {"ops": one["ops"] * n, "bytes": one["bytes"] * n}, ctx.peaks)
    ctx.log(f"uct roofline: {n:.0f} slot-supersteps, {one['bytes']} bytes "
            f"and {one['ops']} ops each; bound by {bound}; least "
            f"{need!r}s of {t!r}s kernel time")
    return 100.0 * need / t
