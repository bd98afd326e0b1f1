"""Training state + schedules."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import needs_server_ef


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    ef_residual: Any          # pytree of f32 residuals (or None) — server EF
    step: jnp.ndarray         # int32 round counter
    seed: jnp.ndarray         # uint32 base seed


def init_state(params, *, server: str, seed: int, mesh=None) -> TrainState:
    """Fresh state around ``params``. The EF residual takes the params'
    placement; with ``mesh`` the two counters are committed replicated on it.
    Placed params plus ``mesh`` give the state the placement the trainers'
    steps return, so step 0 and every later step share one executable."""
    ef = None
    if needs_server_ef(server):
        ef = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    step, seed = jnp.int32(0), jnp.uint32(seed)
    if mesh is not None:
        step, seed = jax.device_put((step, seed), NamedSharding(mesh, P()))
    return TrainState(params=params, ef_residual=ef, step=step, seed=seed)


@dataclasses.dataclass(frozen=True)
class LrSchedule:
    base: float = 1e-3
    warmup: int = 0
    decay_steps: Optional[int] = None   # cosine horizon; None = constant
    min_ratio: float = 0.1

    def __call__(self, step):
        lr = jnp.float32(self.base)
        if self.warmup > 0:
            lr = lr * jnp.minimum(1.0, (step + 1) / self.warmup)
        if self.decay_steps:
            t = jnp.clip((step - self.warmup) / max(self.decay_steps - self.warmup, 1), 0.0, 1.0)
            cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
            lr = lr * (self.min_ratio + (1.0 - self.min_ratio) * cos)
        return lr
