"""Pallas TPU kernel template: generic fused ternary compression.

One kernel body serves the whole ternary family — the probability/symbol rule
(rules.py) is a compile-time specialization, exactly like sparsign's dedicated
kernel: read g (2 or 4 B/coord) in one HBM pass, regenerate the counter-hash
Bernoulli/noise draws in-register, write either the int8 ternary tensor
(1 B/coord) or, in the fused ``*_pack2bit`` variant, the 2-bit packed wire
directly (0.25 B/coord — the int8 ternary tensor never exists in HBM).

Unlike sparsign (whose rule maps 0 -> 0), some rules emit nonzero symbols at
zero input (noisy_sign signs pure noise), so the canonical-view zero padding
must be masked explicitly: positions >= n are forced to 0 so the packed wire
stays bitwise-equal to ``pack2bit(ref(g))`` and the byte-level nnz count stays
exact.

Tiling matches the sparsign kernels: canonical (rows, 512) f32/bf16 input
blocks, (rows, 512) int8 or (rows, 128) uint8 output blocks, grid over rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import RNG_GOLDEN, mix32, pack2bit_quads, uniform24
from repro.kernels.ternary.rules import RULES

# SMEM scalars: a (1, 5) uint32 row
#   [seed, fold(seed,1), fold(seed,2), counter_base, n_valid]
# and the rule's (1, 1) f32 param. The three seeds feed u(0)/u(1)/u(2); rules
# draw lazily, unused streams cost nothing (the hash is only materialized
# when the rule calls u).
N_SCALARS = 5


def _symbols(scalars_ref, param_ref, g_ref, *, rule, block_rows: int, lanes: int):
    counter_base = scalars_ref[0, 3]
    param = param_ref[0, 0]
    n_valid = scalars_ref[0, 4]

    r0 = pl.program_id(0) * block_rows
    rows = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, lanes), 1)
    pos = (jnp.uint32(r0) + rows) * jnp.uint32(lanes) + cols
    idx = pos + counter_base

    def u(salt: int):
        # counter-hash RNG (kernels/common.mix32 — mirrors repro.core.prng);
        # salt picks the host-folded seed: 0 = unfolded, k = fold_seed(seed, k)
        bits = mix32((idx * RNG_GOLDEN) ^ mix32(scalars_ref[0, salt] + RNG_GOLDEN))
        return uniform24(bits)

    g = g_ref[...].astype(jnp.float32)
    # mask the canonical-view padding: rules need not map 0 -> 0
    return jnp.where(pos < n_valid, rule(g, u, param), 0.0)


def _compress_kernel(scalars_ref, param_ref, g_ref, out_ref, *, rule, block_rows, lanes):
    t = _symbols(scalars_ref, param_ref, g_ref, rule=rule, block_rows=block_rows,
                 lanes=lanes)
    out_ref[...] = t.astype(jnp.int8)


def _pack2bit_kernel(scalars_ref, param_ref, g_ref, out_ref, *, rule, block_rows, lanes):
    t = _symbols(scalars_ref, param_ref, g_ref, rule=rule, block_rows=block_rows,
                 lanes=lanes)
    # pack2bit's block-interleaved encoding, still in VMEM (see pack2bit/ref.py)
    out_ref[...] = pack2bit_quads(t, lanes // 4)


@functools.partial(jax.jit, static_argnames=("rule", "block_rows", "interpret"))
def ternary_compress_2d(g2d: jnp.ndarray, scalars: jnp.ndarray, param: jnp.ndarray,
                        *, rule: str, block_rows: int, interpret: bool):
    """g2d: (rows, LANES) f32/bf16; scalars: (1, N_SCALARS) uint32; param:
    (1, 1) f32.
    Returns the (rows, LANES) int8 ternary symbols of RULES[rule]."""
    rows, lanes = g2d.shape
    return pl.pallas_call(
        functools.partial(_compress_kernel, rule=RULES[rule],
                          block_rows=block_rows, lanes=lanes),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
        interpret=interpret,
        name="ternary_compress_2d",
    )(scalars, param, g2d)


@functools.partial(jax.jit, static_argnames=("rule", "block_rows", "interpret"))
def ternary_pack2bit_2d(g2d: jnp.ndarray, scalars: jnp.ndarray, param: jnp.ndarray,
                        *, rule: str, block_rows: int, interpret: bool):
    """Fused compress -> 2-bit packed wire: (rows, LANES) -> (rows, LANES//4)
    uint8, one HBM pass, no int8 ternary intermediate."""
    rows, lanes = g2d.shape
    q = lanes // 4
    return pl.pallas_call(
        functools.partial(_pack2bit_kernel, rule=RULES[rule],
                          block_rows=block_rows, lanes=lanes),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, q), jnp.uint8),
        interpret=interpret,
        name="ternary_pack2bit_2d",
    )(scalars, param, g2d)
