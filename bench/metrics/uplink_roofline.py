"""uplink_roofline (%): algorithmic bytes of the uplink over its time.

Per coordinate: read the gradient (its dtype, 2 B in bfloat16) and write
the 2-bit payload (0.25 B), whatever kernel implements it."""


def read(ctx):
    nbytes = sum(n * (item + 0.25) for n, item in ctx.leaves)
    return ctx.roofline("uplink", nbytes)
