"""Public pack/unpack ops over arbitrary-shape ternary tensors."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.pack2bit.kernel import (pack2bit_2d, unpack2bit_2d,
                                           unpack2bit_sum_2d,
                                           unpack2bit_wsum_2d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack2bit_op(t: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """int8 ternary (any shape) -> packed uint8 of the canonical 2D view.

    Returns the (rows, LANES//4) packed array; pair with ``unpack2bit_op(packed,
    orig_size, orig_shape)`` to invert. The canonical view is part of the wire
    format (see ref.py docstring).
    """
    if interpret is None:
        interpret = common.default_interpret()
    view, _ = common.to_2d(t.reshape(-1))
    br = common.block_rows_for(view.shape[0])
    return pack2bit_2d(view, block_rows=br, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "shape", "interpret"))
def unpack2bit_op(packed: jnp.ndarray, n: int, shape, *, interpret: bool | None = None) -> jnp.ndarray:
    if interpret is None:
        interpret = common.default_interpret()
    br = common.block_rows_for(packed.shape[0])
    t2d = unpack2bit_2d(packed, block_rows=br, interpret=interpret)
    return common.from_2d(t2d, n, shape)


@functools.partial(jax.jit, static_argnames=("n", "shape", "interpret"))
def unpack2bit_sum_op(gathered: jnp.ndarray, n: int, shape, *,
                      interpret: bool | None = None) -> jnp.ndarray:
    """(M, rows, LANES//4) gathered packed votes -> int32 vote sum in ``shape``.

    Fused decode+accumulate (see unpack2bit_sum_2d); the decode side of the
    ``allgather_packed`` wire. Block rows shrink with M so the (M, block, q)
    input block stays within a ~2 MiB VMEM budget at any worker count.
    """
    if interpret is None:
        interpret = common.default_interpret()
    br = common.gather_block_rows(gathered.shape)
    total2d = unpack2bit_sum_2d(gathered, block_rows=br, interpret=interpret)
    return common.from_2d(total2d, n, shape)


@functools.partial(jax.jit, static_argnames=("n", "shape", "interpret"))
def unpack2bit_wsum_op(gathered: jnp.ndarray, weights: jnp.ndarray, n: int,
                       shape, *, interpret: bool | None = None) -> jnp.ndarray:
    """(M, rows, LANES//4) gathered packed votes + (M,) f32 per-worker weights
    -> f32 weighted vote sum ``sum_m weights[m] * votes_m`` in ``shape``.

    The elastic-participation decode of the ``allgather_packed`` wire: weights
    ride the gather as a billed side channel; a dropped worker (zero payload,
    zero weight) contributes exact zeros. Same VMEM budget rule as
    ``unpack2bit_sum_op``.
    """
    if interpret is None:
        interpret = common.default_interpret()
    br = common.gather_block_rows(gathered.shape)
    w = weights.astype(jnp.float32).reshape(1, gathered.shape[0])
    total2d = unpack2bit_wsum_2d(gathered, w, block_rows=br, interpret=interpret)
    return common.from_2d(total2d, n, shape)
