"""jit'd public wrappers for the Golomb/RLE wire kernels: arbitrary
shapes/dtypes, pad -> canonical 2D -> kernel -> (rows, ROW_BYTES) uint8
entropy-coded payload (or back, for the decode-sum).

``sparsign_golomb_op`` matches the registry's ``fused_pack_op`` contract
``(g, param, seed, counter_base, *, interpret=)`` — the plan-time nonzero
fraction ``p`` is keyword-only with a paper-regime default so spec-generic
audits can trace it; the engine passes the wire's configured ``p``
explicitly, and capacity (the static output row count) is a pure function of
``(g.size, p)`` shared with the wire ledger (``ref.golomb_rows``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.golomb import ref as golomb_ref
from repro.kernels.golomb.kernel import (golomb_pack_2d, sparsign_golomb_2d,
                                         ungolomb_sum, ungolomb_wsum)

#: default plan-time nonzero fraction (paper-regime 5%) — only for
#: spec-generic tracing; real wires pass their configured p
DEFAULT_P = 0.05


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def sparsign_golomb_op(
    g: jnp.ndarray,
    budget,
    seed,
    counter_base=0,
    *,
    p: float = DEFAULT_P,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Entropy-coded sparsign wire of ``g`` (any shape, f32/bf16), fused:
    gradient -> coded bytes in one HBM pass, no int8 ternary intermediate.

    Zero padding of the canonical view is harmless: sparsign(0) == 0 emits no
    code, so padded and unpadded messages code identically."""
    if interpret is None:
        interpret = common.default_interpret()
    n = int(g.size)
    view, _ = common.to_2d(g.reshape(-1))
    return sparsign_golomb_2d(view, common.smem_row(jnp.uint32, seed, counter_base),
                              common.smem_row(jnp.float32, budget),
                              b=golomb_ref.rice_b(p),
                              out_rows=golomb_ref.golomb_rows(n, p),
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def golomb_pack_op(
    t: jnp.ndarray,
    *,
    p: float = DEFAULT_P,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Encode an existing ternary message (any shape, int8) — the second
    launch of the two-pass chain (``golomb_pack_op(sparsign_op(g, ...))``),
    byte-identical to the fused op."""
    if interpret is None:
        interpret = common.default_interpret()
    n = int(t.size)
    view, _ = common.to_2d(t.reshape(-1).astype(jnp.int8))
    return golomb_pack_2d(view, b=golomb_ref.rice_b(p),
                          out_rows=golomb_ref.golomb_rows(n, p),
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("size", "shape", "p", "interpret"))
def ungolomb_sum_op(
    gathered: jnp.ndarray,
    size: int,
    shape,
    *,
    p: float = DEFAULT_P,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(M, rows, ROW_BYTES) gathered payloads -> int32 vote sum of ``shape``,
    workers accumulated in strict gather order (pinned against
    ``ref.ungolomb_sum_ref``)."""
    if interpret is None:
        interpret = common.default_interpret()
    total = ungolomb_sum(gathered, n=size, b=golomb_ref.rice_b(p),
                         interpret=interpret)
    return total.reshape(shape)


@functools.partial(jax.jit, static_argnames=("size", "shape", "p", "interpret"))
def ungolomb_wsum_op(
    gathered: jnp.ndarray,
    weights: jnp.ndarray,
    size: int,
    shape,
    *,
    p: float = DEFAULT_P,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(M, rows, ROW_BYTES) gathered payloads + (M,) f32 per-worker weights ->
    f32 weighted vote sum ``sum_m weights[m] * votes_m`` of ``shape``, workers
    accumulated in strict gather order (pinned against
    ``ref.ungolomb_wsum_ref``). The elastic-participation decode of the
    golomb gather wire: weights ride the gather as a billed side channel."""
    if interpret is None:
        interpret = common.default_interpret()
    m = int(gathered.shape[0])
    w = weights.astype(jnp.float32).reshape(1, m)
    total = ungolomb_wsum(gathered, w, n=size, b=golomb_ref.rice_b(p),
                          interpret=interpret)
    return total.reshape(shape)
