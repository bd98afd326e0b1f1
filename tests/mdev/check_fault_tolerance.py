"""Fault-tolerance + elastic-participation checks on 8 host devices.

1. Crash/restart: run A trains 8 steps straight; run B checkpoints every 2
   steps, dies (injected) at step 5, restarts from the checkpoint, finishes.
   Final params must be BITWISE identical (pure-function-of-step data stream +
   deterministic per-round compression seeds).
2. Elastic rescale: checkpoint from a 4-worker mesh restores onto a 2-worker
   mesh and training continues (majority vote is M-invariant).
3. Elastic parity: a ParticipationSpec with uniform weights, zero dropout and
   q_frac == quorum/M is BITWISE the legacy fixed-quorum round on every wire
   mode (votes/psum, votes/gather, pack8/gather, decoded/psum), both kernel
   backends, on the real 4-worker data axis.
4. Chaos: 50% per-round report dropout + non-uniform (data-volume) weights on
   every wire — including every gather wire (pack2, pack8, golomb) — trains
   finite, and the billed participation drops below the full fleet.
5. M-invariance: a 4-worker and a 2-worker fleet fed identical aggregate data
   produce BITWISE-identical params under the participation-normalized vote
   (q_frac), while the legacy fixed integer quorum silently freezes the
   smaller fleet — the failure mode the normalization exists to fix.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import shutil

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.dist.collectives import ParticipationSpec
from repro.configs.base import LayerSpec, ModelConfig
from repro.configs.registry import get_config
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.data.synthetic import LMStreamConfig, lm_batch
from repro.models.model import Model
from repro.train import loop as loop_lib
from repro.train.state import LrSchedule, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step

CKPT = "/tmp/repro_ft_ckpt"


def setup(mesh_shape=(4, 2)):
    mesh = make_mesh(mesh_shape, ("data", "model"))
    cfg = get_config("qwen1.5-4b", smoke=True)
    model = Model(cfg)
    comp = CompressionConfig(compressor="sparsign", budget=BudgetConfig(value=2.0),
                             server="scaled_sign_ef")
    step = build_train_step(model, TrainStepConfig(
        compression=comp, lr=LrSchedule(base=0.01), worker_axes=("data",), donate=False), mesh)
    params = model.init(jax.random.PRNGKey(0))
    state = init_state(params, server=comp.server, seed=77)
    stream = LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8, seed=3)
    batch_fn = lambda i: {k: jnp.asarray(v) for k, v in lm_batch(stream, i).items()}
    return mesh, step, state, batch_fn


# --- elastic-participation sections: a tiny dense model (the wire layer does
# --- not care about model size; ~20 extra step builds must stay cheap)

def tiny_model():
    cfg = ModelConfig(name="ft-tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      pattern=(LayerSpec(mixer="attn"),), dtype="float32",
                      attn_chunk=8, q_chunk=8, loss_chunk=8, remat=False)
    return Model(cfg)


def tiny_batch(vocab, rows, step_i):
    rng = np.random.RandomState(1000 + step_i)
    s = 8
    return {
        "inputs": jnp.asarray(rng.randint(0, vocab, (rows, s)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, vocab, (rows, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (rows, s)).astype(jnp.int32),
    }


def run_tiny(mesh_shape, comp, n_steps, batch_of, **cfg_kw):
    """n_steps of the tiny model on a fresh mesh; returns (params, metrics list)."""
    mesh = make_mesh(mesh_shape, ("data", "model"))
    model = tiny_model()
    step = build_train_step(model, TrainStepConfig(
        compression=comp, lr=LrSchedule(base=0.05), worker_axes=("data",),
        donate=False, **cfg_kw), mesh)
    state = init_state(model.init(jax.random.PRNGKey(0)), server=comp.server, seed=7)
    hist = []
    with jax.sharding.set_mesh(mesh):
        for i in range(n_steps):
            state, metrics = step(state, batch_of(i))
            hist.append({k: float(v) for k, v in metrics.items()
                         if jnp.asarray(v).size == 1})
    return jax.tree_util.tree_map(np.asarray, state.params), hist


WIRE_MODES = [  # (tag, compressor, server, vote_impl, quorum, extra cfg)
    ("votes/psum   ", "sparsign", "majority_vote", "psum", 2, {}),
    ("votes/gather ", "sparsign", "majority_vote", "allgather_packed", 2, {}),
    ("pack8/gather ", "qsgd8", "mean", "allgather_packed", 1, {}),
    ("decoded/psum ", "qsgd8", "mean", "psum", 1, {}),
]
OTHER = "interpret" if jax.default_backend() != "tpu" else "pallas"


def elastic_parity():
    m = 4
    batch_of = lambda i: tiny_batch(64, rows=8, step_i=i)
    for tag, compressor, server, vote_impl, quorum, extra in WIRE_MODES:
        comp = CompressionConfig(compressor=compressor,
                                 budget=BudgetConfig(value=1.0), server=server)
        legacy, _ = run_tiny((m, 2), comp, 2, batch_of, vote_impl=vote_impl,
                             quorum=quorum, **extra)
        for backend in ("jnp", OTHER):
            spec = ParticipationSpec(q_frac=quorum / m)
            elastic, hist = run_tiny((m, 2), comp, 2, batch_of,
                                     vote_impl=vote_impl, quorum=quorum,
                                     participation=spec, backend=backend, **extra)
            for (ka, a), (_, b) in zip(
                    jax.tree_util.tree_flatten_with_path(legacy)[0],
                    jax.tree_util.tree_flatten_with_path(elastic)[0]):
                assert np.array_equal(a, b), \
                    (tag, backend, jax.tree_util.keystr(ka))
            assert all(h["participated"] == m for h in hist)
        print(f"OK elastic parity {tag} weighted(q_frac={quorum}/{m}) == "
              f"legacy(quorum={quorum}) bitwise, both backends")


CHAOS_WIRES = [  # every wire; gather wires (pack2, pack8, golomb) included
    ("votes/psum   ", "sparsign", "majority_vote", "psum", {}),
    ("votes/gather ", "sparsign", "majority_vote", "allgather_packed", {}),
    ("pack8/gather ", "qsgd8", "mean", "allgather_packed", {}),
    ("golomb/gather", "sparsign_golomb", "majority_vote", "allgather_packed",
     {"golomb_p": 0.25}),
    ("decoded/psum ", "qsgd8", "mean", "psum", {}),
]


def chaos():
    m, steps = 4, 4
    spec = ParticipationSpec(weights=(1.5, 0.5, 2.0, 1.0), q_frac=0.5, dropout=0.5)
    batch_of = lambda i: tiny_batch(64, rows=8, step_i=i)
    for tag, compressor, server, vote_impl, extra in CHAOS_WIRES:
        comp = CompressionConfig(compressor=compressor,
                                 budget=BudgetConfig(value=1.0), server=server)
        _, hist = run_tiny((m, 2), comp, steps, batch_of, vote_impl=vote_impl,
                           participation=spec, **extra)
        assert all(np.isfinite(h["loss"]) for h in hist), tag
        parts = [h["participated"] for h in hist]
        assert all(0.0 <= p <= m for p in parts), (tag, parts)
        assert min(parts) < m, \
            (tag, "50% dropout never dropped a report", parts)
        print(f"OK chaos {tag} dropout=0.5 weighted: loss={hist[-1]['loss']:.4f} "
              f"participated={parts}")


def m_invariance():
    # budget 1e38: p = clip(|g| * 1e38, 0, 1) saturates at 1 for every normal
    # float, so sparsign degenerates to the deterministic dense sign(g) and
    # identical worker shards vote unanimously — which is what makes a
    # 4-worker and a 2-worker fleet comparable at all.
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(value=1e38),
                             server="majority_vote")

    def batch_of(dp):
        # every worker's shard is the same 2-row base: the AGGREGATE data is
        # identical across fleet sizes (model axis stays 2 so per-worker
        # math is bitwise too)
        return lambda i: jax.tree_util.tree_map(
            lambda v: jnp.tile(v, (dp,) + (1,) * (v.ndim - 1)),
            tiny_batch(64, rows=2, step_i=i))

    finals = {}
    for dp in (4, 2):
        finals[dp], _ = run_tiny((dp, 2), comp, 4, batch_of(dp),
                                 participation=ParticipationSpec(q_frac=0.75))
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(finals[4])[0],
            jax.tree_util.tree_flatten_with_path(finals[2])[0]):
        assert np.array_equal(a, b), ("M-invariance", jax.tree_util.keystr(ka))
    print("OK M-invariance: 4-worker and 2-worker fleets on identical "
          "aggregate data agree bitwise under q_frac=0.75")

    # the legacy fixed integer quorum does NOT normalize: quorum=3 moves the
    # 4-worker fleet but silently freezes the 2-worker one (|2 sign| < 3
    # everywhere), which is exactly the bug the quorum fraction fixes
    init = jax.tree_util.tree_map(
        np.asarray, tiny_model().init(jax.random.PRNGKey(0)))
    for dp, should_move in ((4, True), (2, False)):
        params, _ = run_tiny((dp, 2), comp, 4, batch_of(dp), quorum=3)
        moved = any(not np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(init)))
        assert moved == should_move, (dp, moved)
    print("OK M-invariance: legacy quorum=3 froze the 2-worker fleet "
          "(and moved the 4-worker one) — q_frac removes the M-dependence")


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    # --- run A: uninterrupted ---
    mesh, step, state, batch_fn = setup()
    with jax.sharding.set_mesh(mesh):
        ref_state, _ = loop_lib.run(step, state, batch_fn,
                                    loop_lib.LoopConfig(total_steps=8, log_every=100))
    # --- run B: checkpoint every 2, die at 5, restart ---
    mesh, step, state, batch_fn = setup()
    cfgB = loop_lib.LoopConfig(total_steps=8, ckpt_dir=CKPT, ckpt_every=2,
                               fail_at_step=5, log_every=100)
    died = False
    try:
        with jax.sharding.set_mesh(mesh):
            loop_lib.run(step, state, batch_fn, cfgB)
    except RuntimeError as e:
        died = True
        print("injected failure:", e)
    assert died
    # restart (fresh everything, as after a pod loss)
    mesh, step, state, batch_fn = setup()
    cfgB2 = loop_lib.LoopConfig(total_steps=8, ckpt_dir=CKPT, ckpt_every=2, log_every=100)
    with jax.sharding.set_mesh(mesh):
        state_b, _ = loop_lib.run(step, state, batch_fn, cfgB2)
    for pa, pb in zip(jax.tree_util.tree_leaves(ref_state.params),
                      jax.tree_util.tree_leaves(state_b.params)):
        assert np.array_equal(np.asarray(pa), np.asarray(pb)), "restart diverged"
    print("OK crash/restart: final params bitwise identical to uninterrupted run")

    # --- elastic: restore the checkpoint on a (2, 4) mesh and keep training ---
    mesh2, step2, state2, batch_fn2 = setup(mesh_shape=(2, 4))
    with jax.sharding.set_mesh(mesh2):
        state2b, hist = loop_lib.run(step2, state2, batch_fn2,
                                     loop_lib.LoopConfig(total_steps=10, ckpt_dir=CKPT,
                                                         ckpt_every=100, log_every=100))
    assert int(state2b.step) == 10
    assert np.isfinite(hist[-1]["loss"])
    print("OK elastic: resumed 4-worker checkpoint on a 2-worker mesh; loss",
          hist[-1]["loss"])

    # --- elastic participation: parity, chaos, M-invariance ---
    elastic_parity()
    chaos()
    m_invariance()


if __name__ == "__main__":
    main()
