"""Pallas TPU kernel: fused server error-feedback step (Eq. 8).

Unfused, the server step is 4 memory passes over param-sized fp32 arrays
(add, sign, scale-mul, subtract); fused it is one read pair + one write pair.
With the ~1.6 B params of a jamba model shard this is the second-largest
memory-bound op of a round after the gradient itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(scale_ref, d_ref, e_ref, out_ref, newe_ref):
    scale = scale_ref[0, 0]
    acc = d_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    out = scale * jnp.sign(acc)
    out_ref[...] = out
    newe_ref[...] = acc - out


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ef_server_2d(d2d, e2d, scale, *, block_rows: int, interpret: bool):
    rows, lanes = d2d.shape
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        ),
        interpret=interpret,
        name="ef_server_2d",
    )(jnp.asarray(scale, jnp.float32).reshape(1, 1), d2d, e2d)
