"""Pallas kernels: fused sparsign -> Golomb/RLE entropy-coded uplink wire,
the encode-only (two-pass) variant, and the fused gather decode-sum.

One HBM pass from gradient to wire bytes: read g (2 or 4 B/coord), write the
entropy-coded stream (~(2+b)*p bits/coord at plan fraction p — sub-0.5
bits/coord in the paper regime, vs pack2bit's flat 2). The Bernoulli draws
are regenerated in-register from the counter hash (identical stream to
``repro.core.prng`` / the sparsign kernel) and the ternary symbols are coded
while still in VMEM — the int8 ternary tensor never exists in HBM. Emission
and decode are the SAME helpers the jnp reference uses
(``kernels.golomb.ref``), so kernel == ref bitwise holds by construction.

Sequential entropy coding needs the whole message in one kernel instance, so
these kernels run a single-cell grid with the full canonical view as one
block (VMEM-bounded by the engine's chunking for huge leaves; bucket slots
are per-leaf messages and stay small). The emission helper leans on gather/
scatter/prefix-sum jnp ops that interpret mode executes directly and Mosaic
does not lower (``cummax`` in the encoders, ``dynamic_slice`` in the
decode-sums): these kernels run in interpret mode only. A streaming-grid TPU
lowering (per-block carry of bit offsets in SMEM) is ROADMAP S2's open item.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import RNG_GOLDEN, mix32, uniform24
from repro.kernels.golomb import ref as golomb_ref


def _encode_kernel(seeds_ref, budget_ref, g_ref, out_ref, *, rows: int, lanes: int,
                   b: int, out_rows: int):
    # SMEM: seeds_ref (1, 2) uint32 [seed, counter_base]; budget_ref (1, 1) f32
    seed = seeds_ref[0, 0]
    counter_base = seeds_ref[0, 1]
    budget = budget_ref[0, 0]

    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1)
    idx = r * jnp.uint32(lanes) + c + counter_base

    # counter-hash RNG (kernels/common.mix32 — mirrors repro.core.prng exactly)
    hbits = mix32((idx * RNG_GOLDEN) ^ mix32(seed + RNG_GOLDEN))
    u = uniform24(hbits)

    g = g_ref[...].astype(jnp.float32)
    prob = jnp.clip(jnp.abs(g) * budget, 0.0, 1.0)
    t = jnp.where(u < prob, jnp.sign(g), 0.0).astype(jnp.int8)

    out_ref[...] = golomb_ref.emit_stream(t.reshape(-1), b=b, rows=out_rows)


@functools.partial(jax.jit, static_argnames=("b", "out_rows", "interpret"))
def sparsign_golomb_2d(g2d: jnp.ndarray, seeds: jnp.ndarray, budget: jnp.ndarray,
                       *, b: int, out_rows: int, interpret: bool):
    """g2d: (rows, LANES) f32/bf16; seeds: (1,2) uint32 [seed, base]; budget:
    (1,1) f32.

    Returns the (out_rows, ROW_BYTES) uint8 entropy-coded wire of
    sparsign(g2d) — out_rows is the static plan-time capacity
    (``ref.golomb_rows``)."""
    rows, lanes = g2d.shape
    return pl.pallas_call(
        functools.partial(_encode_kernel, rows=rows, lanes=lanes,
                          b=b, out_rows=out_rows),
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, lanes), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((out_rows, golomb_ref.ROW_BYTES),
                               lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, golomb_ref.ROW_BYTES),
                                       jnp.uint8),
        interpret=interpret,
        name="sparsign_golomb_2d",
    )(seeds, budget, g2d)


def _pack_kernel(t_ref, out_ref, *, b: int, out_rows: int):
    out_ref[...] = golomb_ref.emit_stream(t_ref[...].reshape(-1), b=b,
                                          rows=out_rows)


@functools.partial(jax.jit, static_argnames=("b", "out_rows", "interpret"))
def golomb_pack_2d(t2d: jnp.ndarray, *, b: int, out_rows: int, interpret: bool):
    """Encode an existing ternary canonical view (rows, LANES) int8 — the
    second launch of the two-pass chain the fused kernel replaces."""
    rows, lanes = t2d.shape
    return pl.pallas_call(
        functools.partial(_pack_kernel, b=b, out_rows=out_rows),
        grid=(1,),
        in_specs=[pl.BlockSpec((rows, lanes), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((out_rows, golomb_ref.ROW_BYTES),
                               lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, golomb_ref.ROW_BYTES),
                                       jnp.uint8),
        interpret=interpret,
        name="golomb_pack_2d",
    )(t2d)


def _decode_sum_kernel(gathered_ref, out_ref, *, n: int, b: int):
    out_ref[...] = golomb_ref.decode_sum_workers(gathered_ref[...], n, b=b)


@functools.partial(jax.jit, static_argnames=("n", "b", "interpret"))
def ungolomb_sum(gathered: jnp.ndarray, *, n: int, b: int, interpret: bool):
    """(M, rows, ROW_BYTES) gathered payloads -> (n,) int32 vote sum, workers
    accumulated in strict gather order (the shared ref helper)."""
    m, rows, width = gathered.shape
    return pl.pallas_call(
        functools.partial(_decode_sum_kernel, n=n, b=b),
        grid=(1,),
        in_specs=[pl.BlockSpec((m, rows, width), lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((n,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
        name="ungolomb_sum",
    )(gathered)


def _decode_wsum_kernel(w_ref, gathered_ref, out_ref, *, n: int, b: int):
    # w_ref: (1, M) f32 per-worker weights in SMEM (the pack8 scales idiom)
    out_ref[...] = golomb_ref.decode_wsum_workers(
        gathered_ref[...], w_ref[0, :], n, b=b)


@functools.partial(jax.jit, static_argnames=("n", "b", "interpret"))
def ungolomb_wsum(gathered: jnp.ndarray, w: jnp.ndarray, *, n: int, b: int,
                  interpret: bool):
    """(M, rows, ROW_BYTES) gathered payloads + (1, M) f32 weights -> (n,)
    f32 weighted vote sum, workers accumulated in strict gather order (the
    shared ref helper — kernel == ref bitwise by construction)."""
    m, rows, width = gathered.shape
    return pl.pallas_call(
        functools.partial(_decode_wsum_kernel, n=n, b=b),
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, rows, width), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
        name="ungolomb_wsum",
    )(w, gathered)
