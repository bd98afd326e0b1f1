"""Multi-device equivalence check for the simple-mode train step.

Runs on 8 host CPU devices: mesh (4 data, 2 model). Asserts the mesh train_step
update equals an explicit M=4-worker oracle (same seeds, same counters) built
with plain vmap on a single logical device view.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.configs.registry import get_config
from repro.core import prng
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.core.compressors import get_compressor
from repro.models.model import Model
from repro.train import sampling
from repro.train.state import LrSchedule, TrainState, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step

def make_batch(cfg, b, s, key=0):
    rng = np.random.RandomState(key)
    return {
        "inputs": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }

def oracle_step(model, params, batch, comp, lr, n_workers, seed):
    """Explicit per-worker reference (no mesh)."""
    state_step = jnp.int32(0)
    rseed = sampling.round_seed(jnp.uint32(seed), state_step)
    fn = get_compressor(comp.compressor)
    loss_fn = lambda p, b: model.loss(p, b)[0]
    # split batch into worker microbatches
    def worker_grads(w):
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((n_workers, -1) + x.shape[1:])[w], batch)
        return jax.grad(loss_fn)(params, micro)
    leaves0, treedef = jax.tree_util.tree_flatten(params)
    vote_sums = [jnp.zeros(l.shape, jnp.int32) for l in leaves0]
    for w in range(n_workers):
        grads = worker_grads(w)
        wseed = prng.fold_seed(rseed, 0x5EED) + jnp.uint32(w) * jnp.uint32(0x9E3779B9)
        gl = jax.tree_util.tree_flatten(grads)[0]
        for i, g in enumerate(gl):
            seed_i = prng.fold_seed(wseed, i)
            msg = fn(g, budget=jnp.float32(comp.budget.value), seed=seed_i, counter_base=0)
            vote_sums[i] = vote_sums[i] + msg.values.astype(jnp.int32)
    new_leaves = [
        (p.astype(jnp.float32) - lr * jnp.sign(v).astype(jnp.float32)).astype(p.dtype)
        for p, v in zip(leaves0, vote_sums)]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)

def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen1.5-4b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    comp = CompressionConfig(compressor="sparsign", budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    lr_sched = LrSchedule(base=0.01)
    scfg = TrainStepConfig(compression=comp, lr=lr_sched, worker_axes=("data",), donate=False)
    step = build_train_step(model, scfg, mesh)
    state = init_state(params, server=comp.server, seed=1234)
    batch = make_batch(cfg, b=8, s=16)

    with jax.sharding.set_mesh(mesh):
        new_state, metrics = step(state, batch)
    got = jax.tree_util.tree_map(np.asarray, new_state.params)
    want = jax.tree_util.tree_map(np.asarray, oracle_step(model, params, batch, comp, 0.01, 4, 1234))
    flat_g, _ = jax.tree_util.tree_flatten(got)
    flat_w, _ = jax.tree_util.tree_flatten(want)
    n_diff_total = 0
    for a, b in zip(flat_g, flat_w):
        if not np.array_equal(a, b):
            n_diff_total += int((a != b).sum())
    # bf16/f32 grad bit-level nondeterminism across shardings could flip marginal
    # Bernoulli outcomes; with f32 smoke config updates must match exactly.
    assert n_diff_total == 0, f"{n_diff_total} mismatched coordinates"
    print("OK simple-step == 4-worker oracle (majority vote, sparsign)")
    print("metrics:", {k: float(v) for k, v in metrics.items()})

    # engine backend check: the same step built on the Pallas kernels
    # (interpret mode on CPU) must match the jnp-backend oracle bitwise —
    # the oracle above is the pre-refactor reference stream (raw compressors,
    # no engine), so this pins kernels == engine == pre-refactor in one shot.
    scfg_i = TrainStepConfig(compression=comp, lr=lr_sched, worker_axes=("data",),
                             donate=False, backend="interpret")
    step_i = build_train_step(model, scfg_i, mesh)
    with jax.sharding.set_mesh(mesh):
        st_i, _ = step_i(state, batch)
    flat_i = jax.tree_util.tree_flatten(
        jax.tree_util.tree_map(np.asarray, st_i.params))[0]
    ndiff_i = sum(int((a != b).sum()) for a, b in zip(flat_i, flat_w))
    assert ndiff_i == 0, f"interpret backend: {ndiff_i} mismatched coordinates"
    print("OK engine interpret backend == pre-refactor oracle (bitwise)")

    # EF server variant runs + residual finite
    comp2 = CompressionConfig(compressor="sparsign", budget=BudgetConfig(kind="fixed", value=2.0),
                              server="scaled_sign_ef")
    scfg2 = TrainStepConfig(compression=comp2, lr=lr_sched, worker_axes=("data",), donate=False)
    step2 = build_train_step(model, scfg2, mesh)
    state2 = init_state(params, server=comp2.server, seed=99)
    with jax.sharding.set_mesh(mesh):
        s2, m2 = step2(state2, batch)
        s2, m2 = step2(s2, batch)
    efn = sum(float(jnp.sum(x**2)) for x in jax.tree_util.tree_leaves(s2.ef_residual))
    assert np.isfinite(efn) and efn > 0
    print("OK EF server 2 rounds, residual sq-norm:", efn)

    # local steps (tau=2) path compiles + runs
    comp3 = CompressionConfig(compressor="sparsign", budget=BudgetConfig(kind="fixed", value=1.0),
                              server="scaled_sign_ef", local_steps=2, local_budget=10.0)
    scfg3 = TrainStepConfig(compression=comp3, lr=lr_sched, local_lr=0.01, worker_axes=("data",), donate=False)
    step3 = build_train_step(model, scfg3, mesh)
    state3 = init_state(params, server=comp3.server, seed=7)
    tb = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), batch)  # tau leading axis
    with jax.sharding.set_mesh(mesh):
        s3, m3 = step3(state3, tb)
    assert np.isfinite(float(m3["loss"]))
    print("OK local-update (tau=2) EF-SPARSIGNSGD step, loss:", float(m3["loss"]))

if __name__ == "__main__":
    main()
