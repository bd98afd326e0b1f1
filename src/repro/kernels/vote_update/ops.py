"""Public fused vote->update op."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.vote_update.kernel import (vote_update_2d,
                                              vote_update_packed2bit_2d,
                                              weighted_vote_update_2d)


@functools.partial(jax.jit, static_argnames=("quorum", "interpret"))
def vote_update_op(w: jnp.ndarray, votes: jnp.ndarray, eta, *, quorum: int = 1,
                   interpret: bool | None = None) -> jnp.ndarray:
    """w' = w - eta * sign(votes) with quorum deadband; any shape, w dtype preserved."""
    if interpret is None:
        interpret = common.default_interpret()
    w2, n = common.to_2d(w.reshape(-1))
    v2, _ = common.to_2d(votes.reshape(-1))
    br = common.block_rows_for(w2.shape[0])
    out2 = vote_update_2d(w2, v2, common.smem_row(jnp.float32, eta),
                          common.smem_row(jnp.int32, quorum),
                          block_rows=br, interpret=interpret)
    return common.from_2d(out2, n, w.shape)


@functools.partial(jax.jit, static_argnames=("quorum", "interpret"))
def vote_update_packed2bit_op(w: jnp.ndarray, gathered: jnp.ndarray, eta, *,
                              quorum: int = 1,
                              interpret: bool | None = None) -> jnp.ndarray:
    """w' = w - eta * sign(votes) with quorum deadband, where votes is the sum
    of the (M, rows, LANES//4) gathered 2-bit payloads over the canonical view
    of ``w``; any shape, w dtype preserved. Bitwise ``vote_update_op`` of
    ``unpack2bit_sum_op``'s vote sum, with no vote sum in HBM. Block rows
    follow the gathered decode-sums' VMEM rule."""
    if interpret is None:
        interpret = common.default_interpret()
    w2, n = common.to_2d(w.reshape(-1))
    if gathered.shape[1:] != (w2.shape[0], common.LANES // 4):
        raise ValueError(
            f"gathered payloads {gathered.shape} do not pack the canonical "
            f"view {w2.shape} of a {w.shape} parameter")
    out2 = vote_update_packed2bit_2d(
        w2, gathered, common.smem_row(jnp.float32, eta),
        common.smem_row(jnp.int32, quorum),
        block_rows=common.gather_block_rows(gathered.shape),
        interpret=interpret)
    return common.from_2d(out2, n, w.shape)


@functools.partial(jax.jit, static_argnames=("q_frac", "interpret"))
def weighted_vote_update_op(w: jnp.ndarray, wvotes: jnp.ndarray, wtot,
                            eta, *, q_frac: float,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Elastic update: w' = w - eta * sign(wvotes) with the
    participation-normalized deadband ``|wvotes| >= q_frac * wtot``; any
    shape, w dtype preserved. ``wtot`` (realized participation
    ``sum_reporting w_m``) may be a scalar or per-coordinate array —
    broadcast before the canonical view so padded tail coordinates see
    wtot = 0, where the zero-vote sign already produces no step."""
    if interpret is None:
        interpret = common.default_interpret()
    w2, n = common.to_2d(w.reshape(-1))
    v2, _ = common.to_2d(wvotes.astype(jnp.float32).reshape(-1))
    t = jnp.broadcast_to(jnp.asarray(wtot, jnp.float32), wvotes.shape)
    t2, _ = common.to_2d(t.reshape(-1))
    br = common.block_rows_for(w2.shape[0])
    scalars = common.smem_row(jnp.float32, eta, q_frac)
    out2 = weighted_vote_update_2d(w2, v2, t2, scalars, block_rows=br,
                                   interpret=interpret)
    return common.from_2d(out2, n, w.shape)
