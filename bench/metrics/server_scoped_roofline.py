"""server_scoped_roofline (%): the algorithmic bytes of ``server_roofline``
over the time of the server scope."""

import harness


def read(ctx):
    scopes = harness.bench_module("scopes")
    return harness.load_module(harness.BENCH / "metrics" / "server_roofline.py").read(
        scopes.ScopedContext(ctx))
