"""Pallas TPU kernels: 2-bit block-interleaved pack/unpack of ternary streams.

The packed stream is the uplink wire format when a ring all-gather vote is
cheaper than the int8 all-reduce (small worker counts / DCN inter-pod hop):
2 bits/coord vs 8. Pack reads 4 int8 lanes-blocks and writes 1 uint8 block
(5 B/coord-quad moved vs 8 unfused); unpack is the mirror image.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _pack_kernel(t_ref, out_ref, *, quarter: int):
    out_ref[...] = common.pack2bit_quads(t_ref[...], quarter)


def _unpack_kernel(p_ref, out_ref, *, quarter: int):
    p = p_ref[...].astype(jnp.int32)
    for k in range(4):
        out_ref[:, k * quarter:(k + 1) * quarter] = common.decode2bit(p, k).astype(jnp.int8)


def _unpack_sum_kernel(p_ref, out_ref, *, quarter: int, m: int):
    # p_ref block: (M, block_rows, quarter) uint8 — all workers' packed votes
    # for this row block. Decode and accumulate in VMEM; only the int32 vote
    # sum (the psum-equivalent payload) is ever written back. Workers are
    # unrolled so every value stays a 2-D (rows, lanes) tile.
    for k in range(4):
        acc = common.decode2bit(p_ref[0].astype(jnp.int32), k)
        for i in range(1, m):
            acc = acc + common.decode2bit(p_ref[i].astype(jnp.int32), k)
        out_ref[:, k * quarter:(k + 1) * quarter] = acc


def _unpack_wsum_kernel(w_ref, p_ref, out_ref, *, quarter: int, m: int):
    # Elastic-participation decode: (M, block_rows, quarter) packed votes plus
    # (1, M) f32 per-worker weights in SMEM (the pack8 scales idiom). The
    # accumulator unrolls strictly in worker order so the float sum associates
    # exactly like the eager-loop oracle; a masked-out worker's zero payload
    # AND zero weight both force exact-zero contributions.
    for k in range(4):
        votes = [common.decode2bit(p_ref[i].astype(jnp.int32), k).astype(jnp.float32)
                 for i in range(m)]
        # zero seed (not acc = first term): a zero weight times a -1 vote is
        # -0.0, and the oracle's 0.0 + (-0.0) == +0.0 must be reproduced
        acc = jnp.zeros_like(votes[0])
        for i in range(m):
            acc = acc + votes[i] * w_ref[0, i]
        out_ref[:, k * quarter:(k + 1) * quarter] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pack2bit_2d(t2d: jnp.ndarray, *, block_rows: int, interpret: bool) -> jnp.ndarray:
    rows, lanes = t2d.shape
    q = lanes // 4
    return pl.pallas_call(
        functools.partial(_pack_kernel, quarter=q),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, q), jnp.uint8),
        interpret=interpret,
        name="pack2bit_2d",
    )(t2d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def unpack2bit_sum_2d(p3d: jnp.ndarray, *, block_rows: int, interpret: bool) -> jnp.ndarray:
    """(M, rows, q) packed worker votes -> (rows, 4q) int32 vote sum.

    Fused decode+accumulate for the all-gather wire: the gathered 2-bit bytes
    are read once and reduced in VMEM, so the (M, rows, LANES) int8 ternary
    tensor of the unfused vmap(unpack)->sum chain never touches HBM
    (0.25*M + 4 B/coord moved vs 0.25*M + M + M*4 + 4)."""
    m, rows, q = p3d.shape
    lanes = q * 4
    return pl.pallas_call(
        functools.partial(_unpack_sum_kernel, quarter=q, m=m),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((m, block_rows, q), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
        name="unpack2bit_sum_2d",
    )(p3d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def unpack2bit_wsum_2d(p3d: jnp.ndarray, w: jnp.ndarray, *, block_rows: int,
                       interpret: bool) -> jnp.ndarray:
    """(M, rows, q) packed worker votes + (1, M) f32 weights -> (rows, 4q)
    f32 weighted vote sum (the elastic-participation decode of the
    ``allgather_packed`` wire). Same fused decode+accumulate discipline as
    ``unpack2bit_sum_2d`` with the per-worker weights riding in SMEM."""
    m, rows, q = p3d.shape
    lanes = q * 4
    return pl.pallas_call(
        functools.partial(_unpack_wsum_kernel, quarter=q, m=m),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, block_rows, q), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        interpret=interpret,
        name="unpack2bit_wsum_2d",
    )(w, p3d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def unpack2bit_2d(p2d: jnp.ndarray, *, block_rows: int, interpret: bool) -> jnp.ndarray:
    rows, q = p2d.shape
    lanes = q * 4
    return pl.pallas_call(
        functools.partial(_unpack_kernel, quarter=q),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, q), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
        interpret=interpret,
        name="unpack2bit_2d",
    )(p2d)
