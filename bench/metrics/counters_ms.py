"""counters_ms: device self time per step of the step's own metrics (the
counters scope: nonzero counts of the messages, the metrics' psums), what
the program's counters cost on the hot path."""

import harness


def read(ctx):
    return harness.bench_module("scopes").layer_ms(ctx, "counters")
