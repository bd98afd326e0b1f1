"""End-to-end driver: pretrain a ~100M-parameter LM with compressed gradient
exchange on a multi-device mesh — deliverable (b)'s training scenario.

    PYTHONPATH=src python examples/distributed_pretrain.py              # ~100M, 300 steps
    PYTHONPATH=src python examples/distributed_pretrain.py --tiny      # CI-speed

Uses 8 forced host CPU devices as a (4 data x 2 model) mesh: the identical
shard_map/GSPMD program a TPU slice runs (only the mesh constructor differs).
Checkpoints + resume are on; kill it mid-run and re-invoke to see the replay.
"""

import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.data.synthetic import LMStreamConfig, lm_batch
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.train import loop as loop_lib
from repro.train.state import LrSchedule, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step


def lm_100m() -> ModelConfig:
    # embed 50k x 640 (32M) + 10 blocks x ~4.9M + untied head (32M) ~= 114M params
    return ModelConfig(
        name="lm-100m", family="dense", n_layers=10, d_model=640, n_heads=10,
        n_kv_heads=5, d_ff=1712, vocab_size=50000,
        pattern=(LayerSpec(mixer="attn"),), dtype="float32",
        attn_chunk=128, q_chunk=64, loss_chunk=64)


def lm_tiny() -> ModelConfig:
    return ModelConfig(
        name="lm-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=512,
        pattern=(LayerSpec(mixer="attn"),), dtype="float32",
        attn_chunk=32, q_chunk=32, loss_chunk=32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_pretrain_ckpt")
    args = ap.parse_args(argv)

    cfg = lm_tiny() if args.tiny else lm_100m()
    steps = args.steps or (30 if args.tiny else 300)
    seq = args.seq_len or (32 if args.tiny else 128)
    model = Model(cfg)
    n_params = cfg.param_count()
    print(f"model: {cfg.name}, {n_params/1e6:.1f}M params; {steps} steps, "
          f"batch {args.batch} x seq {seq}")

    mesh = make_mesh((4, 2), ("data", "model"))
    comp = CompressionConfig(compressor="sparsign", budget=BudgetConfig(value=1.0),
                             server="scaled_sign_ef")
    step = build_train_step(model, TrainStepConfig(
        compression=comp, lr=LrSchedule(base=2e-3, warmup=2 if args.tiny else 20),
        worker_axes=("data",)), mesh)
    params = model.init(jax.random.PRNGKey(0))
    state = init_state(params, server=comp.server, seed=1)

    stream = LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                            global_batch=args.batch, seed=5)
    batch_fn = lambda i: {k: jnp.asarray(v) for k, v in lm_batch(stream, i).items()}

    lcfg = loop_lib.LoopConfig(total_steps=steps, ckpt_dir=args.ckpt_dir,
                               ckpt_every=max(10, steps // 5), log_every=max(1, steps // 20))
    with jax.sharding.set_mesh(mesh):
        state, history = loop_lib.run(step, state, batch_fn, lcfg)
    if not history:
        print(f"\nnothing to do: checkpoint in {args.ckpt_dir} is already at "
              f"step {int(state.step)} >= {steps} (delete it to re-run)")
        return
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"\nloss: {first:.4f} -> {last:.4f} over {steps} steps "
          f"({'improved' if last < first else 'NO IMPROVEMENT'}); "
          f"mean vote sparsity {history[-1]['nnz_frac']:.4f}")


if __name__ == "__main__":
    main()
