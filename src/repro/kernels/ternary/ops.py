"""jit'd public wrappers for the generic ternary kernel template: arbitrary
shapes/dtypes, pad -> canonical 2D -> kernel -> int8 tensor or packed wire.

``ternary_compress_op``/``ternary_pack2bit_op`` take the rule name as a static
argument; the named partials at the bottom are what the CompressorSpec
registry installs as ``pallas_op``/``fused_pack_op`` — every entry shares the
uniform signature ``(g, param, seed, counter_base, *, interpret=None)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import prng
from repro.kernels import common
from repro.kernels.ternary.kernel import (N_SCALARS, ternary_compress_2d,
                                          ternary_pack2bit_2d)


def _scalars(param, seed, counter_base, n_valid):
    """The (1, N_SCALARS) uint32 and (1, 1) f32 SMEM payloads; seed folds
    happen host-side so the kernel's u(salt) is a pure table read (see
    kernel.py layout)."""
    ints = common.smem_row(jnp.uint32, seed, prng.fold_seed(seed, 1),
                           prng.fold_seed(seed, 2), counter_base, n_valid)
    assert ints.shape == (1, N_SCALARS)
    return ints, common.smem_row(jnp.float32, param)


@functools.partial(jax.jit, static_argnames=("rule", "interpret", "block_rows"))
def ternary_compress_op(
    g: jnp.ndarray,
    param,
    seed,
    counter_base=0,
    *,
    rule: str,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """int8 ternary RULES[rule](g) (any shape, f32/bf16) via the Pallas template."""
    if interpret is None:
        interpret = common.default_interpret()
    view, n = common.to_2d(g.reshape(-1))
    br = block_rows or common.block_rows_for(view.shape[0])
    out2d = ternary_compress_2d(view, *_scalars(param, seed, counter_base, n),
                                rule=rule, block_rows=br, interpret=interpret)
    return common.from_2d(out2d, n, g.shape)


@functools.partial(jax.jit, static_argnames=("rule", "interpret", "block_rows"))
def ternary_pack2bit_op(
    g: jnp.ndarray,
    param,
    seed,
    counter_base=0,
    *,
    rule: str,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> jnp.ndarray:
    """2-bit packed wire of RULES[rule](g), fused — one HBM pass, bitwise equal
    to ``pack2bit_op(ternary_compress_op(g, ...))`` (padding masked in-kernel,
    so rules that don't map 0 -> 0, e.g. noisy_sign, still pad to zero codes)."""
    if interpret is None:
        interpret = common.default_interpret()
    view, n = common.to_2d(g.reshape(-1))
    br = block_rows or common.block_rows_for(view.shape[0])
    return ternary_pack2bit_2d(view, *_scalars(param, seed, counter_base, n),
                               rule=rule, block_rows=br, interpret=interpret)


# ---------------------------------------------------------------------------
# Registry instantiations (CompressorSpec.pallas_op / fused_pack_op)
# ---------------------------------------------------------------------------

sign_op = functools.partial(ternary_compress_op, rule="sign")
sign_pack2bit_op = functools.partial(ternary_pack2bit_op, rule="sign")
noisy_sign_op = functools.partial(ternary_compress_op, rule="noisy_sign")
noisy_sign_pack2bit_op = functools.partial(ternary_pack2bit_op, rule="noisy_sign")
stochastic_ternary_op = functools.partial(ternary_compress_op, rule="stochastic_ternary")
stochastic_ternary_pack2bit_op = functools.partial(ternary_pack2bit_op, rule="stochastic_ternary")
