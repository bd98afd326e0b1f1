"""jit'd public wrapper for the sparsign kernel: arbitrary shapes/dtypes,
pad -> canonical 2D -> kernel -> unpad."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.sparsign.kernel import sparsign_2d


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def sparsign_op(
    g: jnp.ndarray,
    budget,
    seed,
    counter_base=0,
    *,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """int8 ternary sparsign of ``g`` (any shape, f32/bf16) via the Pallas kernel."""
    if interpret is None:
        interpret = common.default_interpret()
    view, n = common.to_2d(g.reshape(-1))
    br = block_rows or common.block_rows_for(view.shape[0])
    out2d = sparsign_2d(view, common.smem_row(jnp.uint32, seed, counter_base),
                        common.smem_row(jnp.float32, budget),
                        block_rows=br, interpret=interpret)
    return common.from_2d(out2d, n, g.shape)
