"""uplink_ms: device time per step of the uplink compression (sparsign and
2-bit packing)."""


def read(ctx):
    return ctx.layer_ms("uplink")
