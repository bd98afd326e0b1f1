"""jit'd public wrappers for the pack8 (8-bit QSGD) wire kernels: arbitrary
shapes/dtypes, pad -> canonical 2D -> kernel -> int8 wire payload (or back).

``qsgd8_op``/``qsgd8_pack8_op`` share the registry's uniform signature
``(g, param, seed, counter_base, *, interpret=None)`` — they are what the
qsgd8 ``CompressorSpec`` installs as ``pallas_op``/``fused_pack_op``. The
payload of the fused op is the wire-native canonical (rows, LANES) int8 view;
``qsgd8_op`` unpads back to the leaf shape for the non-wire (decoded) path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.pack8.kernel import qsgd8_pack8_2d, unpack8_sum_2d


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def qsgd8_pack8_op(
    g: jnp.ndarray,
    param,
    seed,
    counter_base=0,
    *,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """Fused quantize -> 8-bit wire: (any shape, f32/bf16) -> (rows, LANES)
    int8 signed levels, one HBM pass, bitwise equal to
    ``to_2d(qsgd8_levels_ref(g, ...))`` (zero padding quantizes to level 0)."""
    if interpret is None:
        interpret = common.default_interpret()
    view, _ = common.to_2d(g.reshape(-1))
    br = block_rows or common.block_rows_for(view.shape[0])
    return qsgd8_pack8_2d(view, common.smem_row(jnp.uint32, seed, counter_base),
                          common.smem_row(jnp.float32, param),
                          block_rows=br, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def qsgd8_op(
    g: jnp.ndarray,
    param,
    seed,
    counter_base=0,
    *,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """int8 signed qsgd8 levels in the leaf shape (the decoded-wire path)."""
    out2d = qsgd8_pack8_op(g, param, seed, counter_base,
                           interpret=interpret, block_rows=block_rows)
    return common.from_2d(out2d, g.size, g.shape)


@functools.partial(jax.jit, static_argnames=("n", "shape", "interpret"))
def unpack8_sum_op(gathered: jnp.ndarray, scales: jnp.ndarray, n: int, shape, *,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(M, rows, LANES) gathered int8 levels + (M,) f32 scales -> f32 decoded
    sum in ``shape``: sum_m scales[m] * levels[m], accumulated in VMEM in
    worker order (the decode side of the pack8 all-gather wire). The grid
    tiles rows AND worker chunks, so the in-flight (m_chunk, block, LANES)
    int8 block plus its f32 decode scratch stay within a ~2.5 MiB VMEM budget
    at any worker count (block rows cannot shrink below the sublane tile, so
    chunking the worker axis is what bounds large M).
    """
    if interpret is None:
        interpret = common.default_interpret()
    m, rows, lanes = gathered.shape
    br = common.block_rows_for(rows)
    # 5 B per (worker, coord) in flight: int8 input block + f32 decode scratch
    want_chunk = max(1, (1 << 19) // max(1, br * lanes))
    m_chunk = min(m, want_chunk)
    while m % m_chunk:        # largest divisor of M <= the VMEM-budget chunk
        m_chunk -= 1
    total2d = unpack8_sum_2d(gathered, scales.astype(jnp.float32).reshape(1, m),
                             block_rows=br, m_chunk=m_chunk, interpret=interpret)
    return common.from_2d(total2d, n, shape)
