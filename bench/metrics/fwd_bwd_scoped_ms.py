"""fwd_bwd_scoped_ms: device self time per step of the fwd_bwd scope:
forward, backward, rematerialised forward and loss."""

import harness


def read(ctx):
    return harness.bench_module("scopes").layer_ms(ctx, "fwd_bwd")
