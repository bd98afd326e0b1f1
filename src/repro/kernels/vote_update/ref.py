"""Oracle for the fused vote->parameter-update: w' = w - eta * sign(votes).

Optionally applies a quorum threshold (beyond-paper knob): coordinates with
|votes| < quorum produce no update — a robustness/deadband filter on top of the
majority vote (quorum=1 is the paper's rule: any nonzero sum moves).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.pack2bit.ref import unpack2bit_sum_ref


def vote_update_ref(w: jnp.ndarray, votes: jnp.ndarray, eta, quorum: int = 1) -> jnp.ndarray:
    v = votes.astype(jnp.int32)
    step = jnp.where(jnp.abs(v) >= quorum, jnp.sign(v), 0).astype(jnp.float32)
    return (w.astype(jnp.float32) - jnp.float32(eta) * step).astype(w.dtype)


def vote_update_packed2bit_ref(w: jnp.ndarray, gathered: jnp.ndarray, eta,
                               quorum: int = 1) -> jnp.ndarray:
    """Oracle for the fused decode + update: the unfused chain, which decodes
    the (M, rows, L//4) gathered 2-bit payloads into an int32 vote sum in the
    shape of ``w`` and applies ``vote_update_ref``. (The wire's narrowing of
    the sum to int8 or int16 holds every value in [-M, M], so it is left out.)"""
    votes = common.from_2d(unpack2bit_sum_ref(gathered), w.size, w.shape)
    return vote_update_ref(w, votes, eta, quorum)


def weighted_vote_update_ref(w: jnp.ndarray, wvotes: jnp.ndarray,
                             wtot: jnp.ndarray, eta, q_frac: float) -> jnp.ndarray:
    """Elastic-participation oracle: w' = w - eta * sign(sum_m w_m sign_m)
    where the deadband is ``|sum_m w_m sign_m| >= q_frac * W`` — the quorum
    normalizes to the realized participation ``W = sum_reporting w_m``
    (``wtot``, per coordinate or broadcastable scalar) instead of a fixed
    integer M-quorum. With uniform weights and full participation (W = M,
    q_frac = quorum/M) this is bitwise ``vote_update_ref``: f32 sums of
    ternary votes are exact integers up to 2^24 and the threshold product
    recovers the integer quorum exactly on power-of-two fleets."""
    v = wvotes.astype(jnp.float32)
    thr = jnp.float32(q_frac) * jnp.broadcast_to(
        jnp.asarray(wtot, jnp.float32), v.shape)
    step = jnp.where(jnp.abs(v) >= thr, jnp.sign(v), jnp.float32(0.0))
    return (w.astype(jnp.float32) - jnp.float32(eta) * step).astype(w.dtype)
