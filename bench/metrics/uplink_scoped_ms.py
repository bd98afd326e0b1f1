"""uplink_scoped_ms: device self time per step of the uplink scope: the
gradient made into the 2-bit wire message, with its padding and reshapes."""

import harness


def read(ctx):
    return harness.bench_module("scopes").layer_ms(ctx, "uplink")
