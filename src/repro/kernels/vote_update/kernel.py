"""Pallas TPU kernel: fused majority-vote sign + SGD update.

Consumes the int8/int32 vote sums straight out of the psum collective and
applies w' = w - eta * sign(votes) (with optional quorum deadband) in one pass:
read w (2/4 B) + votes (1/4 B), write w' — versus sign->cast->scale->sub jnp
chain at ~4 passes. The weight buffers are the largest arrays a round touches,
so this is the top memory-roofline win of the optimizer tail.

``vote_update_packed2bit_2d`` is the same update fed by the all-gather wire's
2-bit payloads instead of a vote sum: it decodes and sums the M workers' codes
in VMEM, so the gathered bytes and the parameter are each read once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(eta_ref, quorum_ref, w_ref, v_ref, out_ref):
    # SMEM: eta_ref (1, 1) f32, quorum_ref (1, 1) int32
    eta = eta_ref[0, 0]
    quorum = quorum_ref[0, 0]
    v = v_ref[...].astype(jnp.int32)
    step = jnp.where(jnp.abs(v) >= quorum, jnp.sign(v), 0).astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = (w - eta * step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def vote_update_2d(w2d, v2d, eta, quorum, *, block_rows: int, interpret: bool):
    rows, lanes = w2d.shape
    spec_w = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    spec_v = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM), spec_w, spec_v],
        out_specs=spec_w,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), w2d.dtype),
        interpret=interpret,
        name="vote_update_2d",
    )(eta, quorum, w2d, v2d)


def _packed_kernel(eta_ref, quorum_ref, w_ref, p_ref, out_ref, *, quarter: int,
                   m: int):
    # p_ref block: (M, block_rows, quarter) uint8, every worker's 2-bit codes
    # for this row block. Each quarter of lanes is decoded, summed over the
    # workers and applied in registers: no vote sum leaves VMEM.
    eta = eta_ref[0, 0]
    quorum = quorum_ref[0, 0]
    codes = [p_ref[i].astype(jnp.int32) for i in range(m)]
    for k in range(4):
        v = common.decode2bit(codes[0], k)
        for c in codes[1:]:
            v = v + common.decode2bit(c, k)
        step = jnp.where(jnp.abs(v) >= quorum, jnp.sign(v), 0).astype(jnp.float32)
        cols = slice(k * quarter, (k + 1) * quarter)
        w = w_ref[:, cols].astype(jnp.float32)
        out_ref[:, cols] = (w - eta * step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def vote_update_packed2bit_2d(w2d, p3d, eta, quorum, *, block_rows: int,
                              interpret: bool):
    """(rows, 4q) parameter + (M, rows, q) gathered 2-bit payloads -> the
    updated parameter: decode, vote sum, deadband and SGD step in one pass.
    Moves 0.25 M + 2 x itemsize B per coordinate; the unfused chain also
    writes and reads an int32 vote sum and its narrowed copy."""
    rows, lanes = w2d.shape
    m = p3d.shape[0]
    spec_w = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_packed_kernel, quarter=lanes // 4, m=m),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM), spec_w,
                  pl.BlockSpec((m, block_rows, lanes // 4), lambda i: (0, i, 0))],
        out_specs=spec_w,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), w2d.dtype),
        interpret=interpret,
        name="vote_update_packed2bit_2d",
    )(eta, quorum, w2d, p3d)


def _wkernel(scalars_ref, w_ref, v_ref, t_ref, out_ref):
    # SMEM: scalars_ref (1, 2) f32 [eta, q_frac]
    eta = scalars_ref[0, 0]
    q_frac = scalars_ref[0, 1]
    v = v_ref[...].astype(jnp.float32)
    thr = q_frac * t_ref[...].astype(jnp.float32)
    step = jnp.where(jnp.abs(v) >= thr, jnp.sign(v), jnp.float32(0.0))
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = (w - eta * step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def weighted_vote_update_2d(w2d, v2d, t2d, scalars, *, block_rows: int,
                            interpret: bool):
    """Fused elastic update: w' = w - eta * sign(v) where |v| clears the
    participation-normalized deadband q_frac * W per coordinate. Same grid /
    block discipline as ``vote_update_2d`` with one extra f32 operand (the
    per-coordinate realized participation W)."""
    rows, lanes = w2d.shape
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _wkernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), w2d.dtype),
        interpret=interpret,
        name="weighted_vote_update_2d",
    )(scalars, w2d, v2d, t2d)
