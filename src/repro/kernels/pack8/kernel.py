"""Pallas TPU kernels: the 8-bit QSGD (``pack8``) uplink wire.

``qsgd8_pack8_2d`` is the fused quantize->wire pass: read g (2 or 4 B/coord)
once, regenerate the stochastic-rounding uniforms in-register from the counter
hash (identical stream to ``repro.core.prng``), and write the int8 sign*level
payload (1 B/coord) — neither the f32 uniforms nor an int32 level tensor ever
exist in HBM (the jaxpr pins in tests/benchmarks assert zero int32 HBM
elements). The level clip at 127 is part of the quantizer (see ref.py).

``unpack8_sum_2d`` is the decode side of the ``allgather_packed`` pack8 wire:
the gathered (M, rows, LANES) int8 payloads are decoded with their per-worker
f32 scales (SMEM) and accumulated in VMEM, sequentially in worker order so the
float sum associates exactly like the decoded-psum wire — only the f32 sum
(4 B/coord) is written back; the (M, rows, LANES) f32 decoded tensor of the
unfused chain never materializes.

Tiling matches the ternary kernels: canonical (rows, 512) blocks, rows padded
to the int8 sublane tile, grid over row blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import RNG_GOLDEN, mix32, uniform24
from repro.kernels.pack8.ref import QSGD8_LEVELS


def _qsgd8_kernel(seeds_ref, param_ref, g_ref, out_ref, *, block_rows: int, lanes: int):
    # SMEM: seeds_ref (1, 2) uint32 [seed, counter_base]; param_ref (1, 1) f32
    seed = seeds_ref[0, 0]
    counter_base = seeds_ref[0, 1]
    param = param_ref[0, 0]

    r0 = pl.program_id(0) * block_rows
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 1)
    idx = (jnp.uint32(r0) + rows) * jnp.uint32(lanes) + cols + counter_base

    # counter-hash RNG (kernels/common.mix32 — mirrors repro.core.prng exactly)
    bits = mix32((idx * RNG_GOLDEN) ^ mix32(seed + RNG_GOLDEN))
    u = uniform24(bits)

    g = g_ref[...].astype(jnp.float32)
    r = jnp.abs(g) / jnp.maximum(param, 1e-20)
    l = jnp.floor(r)
    level = jnp.minimum(l + (u < (r - l)).astype(jnp.float32),
                        jnp.float32(QSGD8_LEVELS))
    # canonical-view zero padding maps to level 0 (r=0 -> floor 0, frac 0), so
    # no explicit valid-mask is needed — same property the sparsign kernel uses
    out_ref[...] = (jnp.sign(g) * level).astype(jnp.int8)


def _unpack8_sum_kernel(scales_ref, p_ref, out_ref, dec_ref, *, m_chunk: int):
    # p_ref block: (m_chunk, block_rows, lanes) int8 — one worker-chunk's
    # levels for this row block; scales_ref: (1, M) f32 in SMEM. Decode +
    # accumulate in VMEM, strictly in worker order: the grid's worker-chunk
    # axis is innermost (sequential on TPU), so revisiting the same out block
    # accumulates chunk 0, 1, ... in order, and the unrolled loop keeps order
    # within a chunk — float adds must associate exactly like the psum wire.
    # Chunking bounds VMEM at any worker count (an (M, block, lanes) block
    # would grow linearly in M).
    #
    # The per-worker products round-trip through the dec_ref VMEM scratch
    # before the add chain: a compiler may otherwise contract each mul into
    # its add with a single rounding, and the result would drift off the
    # decoded-psum wire, whose products are materialized (hence rounded) at
    # the collective boundary. The store forces the same rounding point.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # +0.0 seed: x + 0.0 == x bitwise here (int levels * positive scales
        # never produce -0.0), matching the psum stream's no-seed sum
        out_ref[...] = jnp.zeros_like(out_ref)

    for k in range(m_chunk):
        dec_ref[k] = p_ref[k].astype(jnp.float32) * scales_ref[0, j * m_chunk + k]
    acc = out_ref[...]
    for k in range(m_chunk):
        acc = acc + dec_ref[k]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def qsgd8_pack8_2d(g2d: jnp.ndarray, seeds: jnp.ndarray, param: jnp.ndarray, *,
                   block_rows: int, interpret: bool) -> jnp.ndarray:
    """g2d: (rows, LANES) f32/bf16; seeds: (1,2) uint32 [seed, base]; param:
    (1,1) f32.

    Returns the (rows, LANES) int8 signed-level wire payload of qsgd8(g2d)."""
    rows, lanes = g2d.shape
    return pl.pallas_call(
        functools.partial(_qsgd8_kernel, block_rows=block_rows, lanes=lanes),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
        interpret=interpret,
        name="qsgd8_pack8_2d",
    )(seeds, param, g2d)


@functools.partial(jax.jit, static_argnames=("block_rows", "m_chunk", "interpret"))
def unpack8_sum_2d(p3d: jnp.ndarray, scales: jnp.ndarray, *,
                   block_rows: int, m_chunk: int, interpret: bool) -> jnp.ndarray:
    """(M, rows, LANES) int8 worker levels + (1, M) f32 scales -> (rows, LANES)
    f32 decoded sum sum_m scales[m] * levels[m] (worker-order association).
    ``m_chunk`` must divide M; the worker-chunk grid axis is innermost so the
    accumulation over chunks is sequential in worker order."""
    m, rows, lanes = p3d.shape
    assert m % m_chunk == 0, (m, m_chunk)
    return pl.pallas_call(
        functools.partial(_unpack8_sum_kernel, m_chunk=m_chunk),
        grid=(rows // block_rows, m // m_chunk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m_chunk, block_rows, lanes), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m_chunk, block_rows, lanes), jnp.float32)],
        interpret=interpret,
        name="unpack8_sum_2d",
    )(scales, p3d)
