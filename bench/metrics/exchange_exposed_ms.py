"""exchange_exposed_ms: the part of ``exchange_ms`` in which no operation of
another layer runs on the device, per step: the exchange not hidden behind
compute."""

import harness


def read(ctx):
    r = harness.bench_module("scopes").of(ctx)
    return None if r is None else r.exchange_exposed_s / r.steps * 1e3
