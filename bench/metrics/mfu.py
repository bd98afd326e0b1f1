"""mfu (%): the model FLOPs of the traced steps (bench/flops/<config>.py,
no recomputation) over the traced window, the chips and their bf16 peak."""


def read(ctx):
    c, r = ctx.cell, ctx.reduced
    flops = c.flops.train_flops(c.config, batch=c.global_batch,
                                seq=int(c.traffic["seq_len"])) * r.steps
    return flops / r.window_s / (ctx.chips * ctx.peak["bf16_flops_per_s"]) * 100.0
