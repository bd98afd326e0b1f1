"""Shared model plumbing: logical-axis sharding hints, norms, initializers.

Sharding is expressed against *logical* axes ("batch", "seq", "heads", "ff",
"expert", "vocab", ...). The trainer/server installs a logical->mesh mapping
(contextvar); model code never mentions mesh axes. Outside any mapping (unit
tests, FL simulation) hints are no-ops, so the same model runs on one CPU
device unchanged.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

_AXIS_RULES: contextvars.ContextVar[Optional[Mapping[str, Optional[str]]]] = (
    contextvars.ContextVar("repro_axis_rules", default=None)
)
_MESH: contextvars.ContextVar = contextvars.ContextVar("repro_mesh", default=None)


@contextlib.contextmanager
def axis_rules(rules: Mapping[str, Optional[str]], mesh=None):
    """Install logical->mesh axis mapping (e.g. {"heads": "model", "batch": "data"}).

    Under a partial-manual shard_map, pass only the *auto* axes (the manual axes
    are already fixed by the shard_map specs).
    """
    t1 = _AXIS_RULES.set(dict(rules))
    t2 = _MESH.set(mesh)
    try:
        yield
    finally:
        _AXIS_RULES.reset(t1)
        _MESH.reset(t2)


def hint(x: jnp.ndarray, *logical: Optional[str]) -> jnp.ndarray:
    """with_sharding_constraint against logical axes; no-op without rules.

    If two logical axes map to the same mesh axis (e.g. 'seq' and 'ff' both ->
    'model'), the LAST occurrence wins — feature dims trail sequence dims in
    our layouts, and Megatron-style layouts shard features inside blocks and
    sequence between them.
    """
    rules = _AXIS_RULES.get()
    if rules is None:
        return x
    spec = [rules.get(name) if name is not None else None for name in logical]
    seen = {}
    for i, s in enumerate(spec):
        if s is None:
            continue
        key = tuple(s) if isinstance(s, (list, tuple)) else s
        if key in seen:
            spec[seen[key]] = None  # earlier duplicate loses
        seen[key] = i
    if all(s is None for s in spec):
        return x
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        # Inside shard_map / set_mesh the ambient mesh is an AbstractMesh; a
        # NamedSharding built from the concrete mesh MISMATCHES it, so a bare
        # PartitionSpec resolves against it. A constraint may only name Auto
        # axes: entries naming a Manual axis (the shard_map's worker axes)
        # are nulled, since the shard_map specs already fix their placement.
        from repro.dist.sharding import _entry_names
        manual = set(am.manual_axes)
        spec = [None if s is not None and set(_entry_names(s)) & manual else s
                for s in spec]
        if all(s is None for s in spec):
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))
    mesh = _MESH.get()
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
    return x


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# Initializers (used by smoke tests / examples; dry-run uses eval_shape only)
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def key_iter(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub
