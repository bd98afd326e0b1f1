"""Pallas TPU kernel: fused sparsign -> 2-bit packed uplink wire.

One HBM pass from gradient to wire bytes: read g (2 or 4 B/coord), write the
block-interleaved 2-bit stream (0.25 B/coord). The Bernoulli draws are
regenerated in-register from the counter hash (identical stream to
``repro.core.prng`` / the standalone sparsign kernel) and the ternary symbols
are encoded and packed while still in VMEM — the int8 ternary tensor never
exists in HBM. The unfused ``pack2bit_op(sparsign_op(g))`` chain moves
(4+1) + (1+0.25) B/coord over two kernel launches; this kernel moves 4.25 in
one, so the ``allgather_packed`` uplink stops paying for a wire format it
immediately re-reads.

Tiling matches the constituent kernels: canonical (rows, 512) f32/bf16 input
blocks, (rows, 128) uint8 output blocks, grid over row blocks. Bitwise
equality with the two-pass chain is pinned by tests/test_wire.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import RNG_GOLDEN, mix32, pack2bit_quads, uniform24


def _kernel(seeds_ref, budget_ref, g_ref, out_ref, *, block_rows: int, lanes: int):
    # SMEM: seeds_ref (1, 2) uint32 [seed, counter_base]; budget_ref (1, 1) f32
    seed = seeds_ref[0, 0]
    counter_base = seeds_ref[0, 1]
    budget = budget_ref[0, 0]

    r0 = pl.program_id(0) * block_rows
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 1)
    idx = (jnp.uint32(r0) + rows) * jnp.uint32(lanes) + cols + counter_base

    # counter-hash RNG (kernels/common.mix32 — mirrors repro.core.prng exactly)
    c = idx * RNG_GOLDEN
    bits = mix32(c ^ mix32(seed + RNG_GOLDEN))
    u = uniform24(bits)

    g = g_ref[...].astype(jnp.float32)
    p = jnp.clip(jnp.abs(g) * budget, 0.0, 1.0)
    t = jnp.where(u < p, jnp.sign(g), 0.0)

    # pack2bit's block-interleaved encoding, still in VMEM: byte j packs the
    # symbols at lane columns (j, j+L/4, j+2L/4, j+3L/4); 0->00, +1->01, -1->10
    out_ref[...] = pack2bit_quads(t, lanes // 4)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sparsign_pack2bit_2d(g2d: jnp.ndarray, seeds: jnp.ndarray, budget: jnp.ndarray,
                         *, block_rows: int, interpret: bool):
    """g2d: (rows, LANES) f32/bf16; seeds: (1,2) uint32 [seed, base];
    budget: (1,1) f32.

    Returns the (rows, LANES//4) uint8 packed wire of sparsign(g2d)."""
    rows, lanes = g2d.shape
    q = lanes // 4
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows, lanes=lanes),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, q), jnp.uint8),
        interpret=interpret,
        name="sparsign_pack2bit_2d",
    )(seeds, budget, g2d)
