"""fwd_bwd_ms: device time per step of the model's forward and backward
passes (every operation no other layer claims)."""


def read(ctx):
    return ctx.layer_ms("fwd_bwd")
