"""The train step's layers, as ``jax.named_scope`` names.

Each operation of a compiled step keeps the name stack it was traced under in
its ``op_name`` metadata, and the profiler's trace keeps that too. The
innermost of these five names in an operation's stack is its layer:

    fwd_bwd    forward, backward, rematerialised forward and the loss
    uplink     gradient -> wire-native message, with its padding and reshapes
    exchange   the collectives that carry the messages between workers
    server     decode-sum, vote or error-feedback update, parameter write-back
    counters   the step's own metrics (nonzero counts, the metrics' psums)

Scopes are metadata only: they change no operation of the compiled step.
"""

from __future__ import annotations

import functools

import jax

FWD_BWD = "fwd_bwd"
UPLINK = "uplink"
EXCHANGE = "exchange"
SERVER = "server"
COUNTERS = "counters"


def scoped(name: str):
    """Decorator: trace every call of the function under
    ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return wrap
