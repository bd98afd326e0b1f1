"""server_ms: device time per step of the decode-sum of the gathered
payloads and the vote update of the parameters."""


def read(ctx):
    return ctx.layer_ms("server")
