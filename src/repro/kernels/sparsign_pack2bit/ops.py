"""jit'd public wrapper for the fused sparsign->pack2bit kernel: arbitrary
shapes/dtypes, pad -> canonical 2D -> fused kernel -> packed canonical wire.

The output is the (rows, LANES//4) uint8 *canonical-view* packed stream — the
same bytes ``pack2bit_op(sparsign_op(g, ...))`` produces, in one HBM pass.
Invert with ``unpack2bit_op(packed, g.size, g.shape)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.sparsign_pack2bit.kernel import sparsign_pack2bit_2d


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def sparsign_pack2bit_op(
    g: jnp.ndarray,
    budget,
    seed,
    counter_base=0,
    *,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """2-bit packed sparsign wire of ``g`` (any shape, f32/bf16), fused.

    Zero padding of the canonical view is harmless: sparsign(0) == 0 and the
    2-bit code of 0 is 0, exactly what the two-pass chain repads with.
    """
    if interpret is None:
        interpret = common.default_interpret()
    view, _ = common.to_2d(g.reshape(-1))
    br = block_rows or common.block_rows_for(view.shape[0])
    return sparsign_pack2bit_2d(view, common.smem_row(jnp.uint32, seed, counter_base),
                                common.smem_row(jnp.float32, budget),
                                block_rows=br, interpret=interpret)
