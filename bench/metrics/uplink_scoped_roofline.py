"""uplink_scoped_roofline (%): the algorithmic bytes of ``uplink_roofline``
over the time of the uplink scope."""

import harness


def read(ctx):
    scopes = harness.bench_module("scopes")
    return harness.load_module(harness.BENCH / "metrics" / "uplink_roofline.py").read(
        scopes.ScopedContext(ctx))
