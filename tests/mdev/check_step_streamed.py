"""Streamed-mode checks on 8 host devices:
1. streamed(majority_vote) == simple(majority_vote) — same algorithm bit-for-bit
   (identical seeds/counters), modulo float-assoc grad differences.
2. FSDP layout: params actually sharded (per-device bytes < full size).
3. EF server variant runs.
4. bucketed + double-buffered streamed step == per-leaf streamed step bitwise,
   all four wire modes x {jnp, interpret} backends (the comm/compute-overlap
   pipeline must be a pure re-scheduling of the same arithmetic).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.configs.registry import get_config
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.models.model import Model
from repro.train.state import LrSchedule, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step
from repro.train.step_streamed import StreamedStepConfig, build_streamed_train_step, fsdp_param_shardings

def make_batch(cfg, b, s, key=0):
    rng = np.random.RandomState(key)
    return {
        "inputs": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }

def main():
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, b=8, s=16)
    comp = CompressionConfig(compressor="sparsign", budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    lr = LrSchedule(base=0.01)

    # --- simple reference ---
    s_simple = init_state(params, server=comp.server, seed=42)
    step_simple = build_train_step(model, TrainStepConfig(
        compression=comp, lr=lr, worker_axes=("data",), donate=False), mesh)
    with jax.sharding.set_mesh(mesh):
        out_simple, m_simple = step_simple(s_simple, batch)
    ref = jax.tree_util.tree_map(np.asarray, out_simple.params)

    # --- streamed ---
    shardings = fsdp_param_shardings(model, mesh, "data")
    params_sh = jax.tree_util.tree_map(jax.device_put, params, shardings)
    s_str = init_state(params_sh, server=comp.server, seed=42)
    step_str = build_streamed_train_step(model, StreamedStepConfig(
        compression=comp, lr=lr, worker_axes=("data",), fsdp_axis="data", donate=False), mesh)
    with jax.sharding.set_mesh(mesh):
        out_str, m_str = step_str(s_str, batch)
    got = jax.tree_util.tree_map(np.asarray, out_str.params)

    total, ndiff = 0, 0
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(ref)[0],
            jax.tree_util.tree_flatten_with_path(got)[0]):
        total += a.size
        d = int((a != b).sum())
        ndiff += d
        if d: print("  diff in", jax.tree_util.keystr(pa), d)
    frac = ndiff / total
    print(f"streamed vs simple: {ndiff}/{total} coords differ ({frac:.2e})")
    assert frac < 1e-4, frac
    print("loss simple vs streamed:", float(m_simple["loss"]), float(m_str["loss"]))
    assert abs(float(m_simple["loss"]) - float(m_str["loss"])) < 1e-4

    # sharded bytes check
    blk = out_str.params["blocks"][0]["wq"]
    shard_bytes = blk.addressable_shards[0].data.size
    assert shard_bytes < blk.size, "wq not FSDP-sharded"
    print("OK FSDP sharding: wq local", shard_bytes, "of", blk.size)

    # EF variant
    comp_ef = CompressionConfig(compressor="sparsign", budget=BudgetConfig(kind="fixed", value=2.0),
                                server="scaled_sign_ef")
    s_ef = init_state(params_sh, server=comp_ef.server, seed=7)
    # ef residual must be sharded like params
    ef_shardings = jax.tree_util.tree_map(lambda s: s, shardings)
    s_ef.ef_residual = jax.tree_util.tree_map(
        lambda p, sh: jax.device_put(jnp.zeros(p.shape, jnp.float32), sh), params_sh, ef_shardings)
    step_ef = build_streamed_train_step(model, StreamedStepConfig(
        compression=comp_ef, lr=lr, worker_axes=("data",), donate=False), mesh)
    with jax.sharding.set_mesh(mesh):
        o1, m1 = step_ef(s_ef, batch)
        o2, m2 = step_ef(o1, batch)
    assert np.isfinite(float(m2["loss"]))
    efn = sum(float(jnp.sum(x.astype(jnp.float32)**2)) for x in jax.tree_util.tree_leaves(o2.ef_residual))
    assert np.isfinite(efn) and efn > 0
    print("OK streamed EF 2 rounds, loss:", float(m2["loss"]), "resid sq:", efn)

    # --- bucketed + double-buffered == per-leaf, all wire setups x 2 backends
    # (mode_comp picks each setup's budget kind: the golomb setup needs a
    # target_sparsity budget to size the wire's static capacity)
    from repro.analysis.drivers import MODE_SETUPS, mode_comp
    for wmode, (_, server, vote_impl, _) in MODE_SETUPS.items():
        comp_w = mode_comp(wmode)
        for backend in ("jnp", "interpret"):
            ref = None
            for bucketed in (False, True):
                step = build_streamed_train_step(model, StreamedStepConfig(
                    compression=comp_w, lr=lr, worker_axes=("data",),
                    fsdp_axis="data", vote_impl=vote_impl, donate=False,
                    backend=backend, bucketed=bucketed), mesh)
                st = init_state(params_sh, server=comp_w.server, seed=42)
                with jax.sharding.set_mesh(mesh):
                    out, m = step(st, batch)
                got = jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(np.asarray, out.params))
                got.append(np.asarray(m["nnz_frac"]))
                if ref is None:
                    ref = got
                    continue
                nd = sum(int((a != b).sum()) for a, b in zip(got, ref))
                assert nd == 0, f"{wmode}/{backend}: {nd} coords differ"
            print(f"OK streamed bucketed == per-leaf bitwise: {wmode}/{backend}")

if __name__ == "__main__":
    main()
