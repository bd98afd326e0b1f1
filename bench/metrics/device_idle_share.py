"""device_idle_share (%): 1 - (union of the device's busy intervals) / the
traced window, averaged over the chips."""


def read(ctx):
    r = ctx.reduced
    return (1.0 - r.busy_s / r.window_s) * 100.0
