"""Plain reference of one training round of the paper's method.

SPARSIGNSGD with a majority vote (Algorithm 1 of arXiv:2302.09634, tau = 1):
every worker draws a ternary message ``sign(g) * Bernoulli(min(|g| B, 1))``
from its own gradient, the server sums the votes and every worker applies
``w - lr * sign(sum)``. Written in plain jax.numpy and float32, independent of
the trainer under test: the counter-hash stream below follows the trainer's
published seed derivation (murmur3 fmix32, golden-ratio salts), so the same
seed selects the same coordinates and the comparison sees only the
arithmetic.

The model is given by a configuration module with ``init_params(key, cfg)``
and ``loss(params, batch, cfg, q)``: the loss takes the weights as stored
(the configuration's dtype) and computes in float32; ``q`` rounds every
matmul operand (identity in float32, float8 for the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
C1 = np.uint32(0x85EBCA6B)
C2 = np.uint32(0xC2B2AE35)
ROUND_SALT = 0x52D
WORKER_SALT = 0x5EED


def mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * C1
    x = x ^ (x >> 13)
    x = x * C2
    return x ^ (x >> 16)


def fold(seed, salt):
    return mix32(jnp.asarray(seed, jnp.uint32) ^ (jnp.uint32(salt) * GOLDEN))


def uniform(seed, n):
    """float32 uniforms in [0, 1) for coordinates 0..n-1 of one leaf."""
    c = jnp.arange(n, dtype=jnp.uint32) * GOLDEN
    bits = mix32(c ^ mix32(jnp.asarray(seed, jnp.uint32) + GOLDEN))
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def leaf_seeds(base_seed, step, worker, n_leaves):
    """Per-leaf stream seeds of one worker in one round."""
    rseed = fold(base_seed, ROUND_SALT) + jnp.asarray(step, jnp.uint32) * GOLDEN
    wseed = fold(rseed, WORKER_SALT) + jnp.asarray(worker, jnp.uint32) * GOLDEN
    return [fold(wseed, i) for i in range(n_leaves)]


def sparsign(g, budget, seed):
    """Ternary int8 message of one leaf."""
    g = g.astype(jnp.float32)
    p = jnp.clip(jnp.abs(g) * jnp.float32(budget), 0.0, 1.0)
    u = uniform(seed, g.size).reshape(g.shape)
    return jnp.where(u < p, jnp.sign(g), 0.0).astype(jnp.int8)


def learning_rate(step, base, warmup):
    lr = np.float32(base)
    if warmup > 0:
        lr = lr * np.float32(min(1.0, (step + 1) / warmup))
    return float(lr)


def quantizer(precision: str):
    """Operand rounding of every matmul: 'float32' keeps the operand;
    'float8' rounds it to float8_e4m3fn under a per-tensor scale (its largest
    magnitude at the format's largest finite value), the forward precision
    of float8 training; the backward pass sees the rounding as the identity."""
    if precision == "float32":
        return lambda x: x.astype(jnp.float32)
    if precision == "float8":
        big = float(jnp.finfo(jnp.float8_e4m3fn).max)

        def q(x):
            x = x.astype(jnp.float32)
            s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / big)
            y = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(y - x)

        return q
    raise ValueError(f"unknown reference precision {precision!r}")


class Reference:
    """Rounds of the method on one device, workers one after another.

    ``fault`` plants a known defect in the reference put in the trainer's
    place, to read what the comparison says of it: ``half_batch`` (each
    worker's gradient and loss from the first half of its rows, or of its
    one row's tokens) or ``unchanged`` (the step returns its parameters as
    they were)."""

    def __init__(self, model, cfg, *, workers, budget, lr, warmup,
                 precision="float32", fault=None):
        self.model, self.cfg = model, cfg
        self.workers, self.budget = workers, budget
        self.lr, self.warmup = lr, warmup
        self.q = quantizer(precision)
        self.fault = fault
        self._grad = jax.jit(self._worker_grad)
        self._apply = jax.jit(self._apply_votes, donate_argnums=(0,))

    def _worker_grad(self, params, batch, base_seed, step, worker):
        if self.fault == "half_batch":
            rows, seq = batch["inputs"].shape
            batch = ({k: v[:rows // 2] for k, v in batch.items()} if rows > 1
                     else {k: v[:, :seq // 2] for k, v in batch.items()})

        def loss_fn(p):
            return self.model.loss(p, batch, self.cfg, self.q)

        # the loss computes in float32 from the stored weights; each gradient
        # leaf is rounded once, to the dtype its weight is stored in
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(params)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        seeds = leaf_seeds(base_seed, step, worker, len(leaves))
        votes = [sparsign(g, self.budget, s) for g, s in zip(leaves, seeds)]
        norms = jnp.stack([jnp.linalg.norm(g.astype(jnp.float32).reshape(-1))
                           for g in leaves])
        return loss, jax.tree_util.tree_unflatten(treedef, votes), norms

    @staticmethod
    def _apply_votes(params, votes, lr):
        return jax.tree_util.tree_map(
            lambda p, v: (p.astype(jnp.float32)
                          - lr * jnp.sign(v).astype(jnp.float32)).astype(p.dtype),
            params, votes)

    def step(self, params, batches, base_seed, step):
        """One round. ``batches``: one batch per worker. Returns
        (new params, mean loss, per-leaf float32 gradient norms of worker 0)."""
        total, losses, gnorms = None, [], None
        for m, b in enumerate(batches):
            loss, votes, norms = self._grad(params, b, jnp.uint32(base_seed),
                                            jnp.uint32(step), jnp.uint32(m))
            losses.append(float(loss))
            if gnorms is None:
                gnorms = np.asarray(norms)
            total = votes if total is None else jax.tree_util.tree_map(
                jnp.add, total, votes)
            del votes
        if self.fault == "unchanged":
            return params, float(np.mean(losses)), gnorms
        lr = learning_rate(step, self.lr, self.warmup)
        return self._apply(params, total, jnp.float32(lr)), float(np.mean(losses)), gnorms


def param_key(seed: int):
    """The weights' key from any whole number, folded to 64 bits."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


def leaf_norms_from(p0, p, scale=1.0):
    """Per-leaf norms of (p0 - p) * scale, in float32."""
    return jnp.stack([
        jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).reshape(-1))
        * jnp.float32(scale)
        for a, b in zip(jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p))])


def leaf_moves_from(p0, p):
    """int8 sign(p0 - p) of every coordinate."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sign(a.astype(jnp.float32) - b.astype(jnp.float32)
                              ).astype(jnp.int8), p0, p)
