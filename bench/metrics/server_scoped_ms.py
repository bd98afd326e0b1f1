"""server_scoped_ms: device self time per step of the server scope:
decode-sum, vote update and parameter write-back."""

import harness


def read(ctx):
    return harness.bench_module("scopes").layer_ms(ctx, "server")
