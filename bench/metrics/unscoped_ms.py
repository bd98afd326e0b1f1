"""unscoped_ms: device self time per step of the operations under none of
the five layer scopes: the check that the scopes cover the step."""

import harness


def read(ctx):
    return harness.bench_module("scopes").layer_ms(ctx, "unscoped")
