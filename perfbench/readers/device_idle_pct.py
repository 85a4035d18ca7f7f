"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device's op intervals / the stretch), averaged over
the chips in use (device trace)."""


def read(ctx):
    t = ctx.device_times()
    if not ctx.trace["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
