"""exchange_ms: device time per step under the exchange scope, its
synchronous operations and the asynchronous collectives together (the union
of their intervals)."""

import harness


def read(ctx):
    r = harness.bench_module("scopes").of(ctx)
    return None if r is None else r.exchange_s / r.steps * 1e3
