"""server_roofline (%): algorithmic bytes of the decode-sum and vote update
over their time.

Per coordinate: read the M workers' 2-bit payloads (0.25 M B) and read and
write the parameter (2 x its dtype, 4 B in bfloat16). An intermediate vote
accumulator is not counted: it is what a fused server step saves."""


def read(ctx):
    m = ctx.cell.workers
    nbytes = sum(n * (0.25 * m + 2 * item) for n, item in ctx.leaves)
    return ctx.roofline("server", nbytes)
