"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

The default smoke config drives the whole path on a CPU; ``--full`` takes the
registry config at its published widths (what ``chip_smoke.py`` runs on a
TPU). The mesh geometry and trainer mode come from the registry; nothing else
changes. Checkpoints/resume/failure-injection are live here.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config, trainer_mode
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.core.engine import BACKENDS
from repro.data.synthetic import LMStreamConfig, lm_batch
from repro.dist import collectives
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh, worker_axes_of
from repro.models.model import Model
from repro.train import loop as loop_lib
from repro.train.state import LrSchedule, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step
from repro.train.step_streamed import (StreamedStepConfig, build_streamed_train_step,
                                       fsdp_param_shardings)


def build_everything(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg)
    if args.mesh == "host":
        mesh = make_host_mesh(args.host_data, args.host_model)
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    wa = worker_axes_of(mesh)
    comp = CompressionConfig(
        compressor=args.compressor,
        budget=BudgetConfig(kind=args.budget_kind, value=args.budget),
        server=args.server,
        local_steps=args.tau,
        local_budget=args.local_budget,
        worker_sample_fraction=args.participation,
    )
    lr = LrSchedule(base=args.lr, warmup=args.warmup)
    # --ring engages the ring-pipelined gather on the packed uplink wires;
    # None keeps the monolithic all_gather
    ring_rows = ((args.ring_chunk_rows or collectives.DEFAULT_RING_CHUNK_ROWS)
                 if args.ring else None)
    # elastic participation: any of --worker-weights/--quorum-frac/--dropout
    # builds a ParticipationSpec (validated loudly before the step builds) and
    # switches the vote to the weighted, participation-normalized form
    part = None
    if (args.worker_weights is not None or args.quorum_frac is not None
            or args.dropout > 0.0):
        weights = (tuple(float(x) for x in args.worker_weights.split(","))
                   if args.worker_weights else None)
        part = collectives.ParticipationSpec(
            weights=weights, q_frac=args.quorum_frac, dropout=args.dropout)
    mode = args.mode or trainer_mode(args.arch)
    if mode == "simple":
        step = build_train_step(model, TrainStepConfig(
            compression=comp, lr=lr, local_lr=args.local_lr, worker_axes=wa,
            vote_impl=args.vote_impl, quorum=args.quorum,
            backend=args.backend, bucketed=args.bucketed,
            ring_chunk_rows=ring_rows, participation=part), mesh)
        params = jax.device_put(model.init(jax.random.PRNGKey(args.seed)),
                                NamedSharding(mesh, P()))
    else:
        step = build_streamed_train_step(model, StreamedStepConfig(
            compression=comp, lr=lr, worker_axes=wa,
            vote_impl=args.vote_impl, quorum=args.quorum,
            backend=args.backend, bucketed=args.bucketed,
            ring_chunk_rows=ring_rows, participation=part), mesh)
        params = model.init(jax.random.PRNGKey(args.seed))
        params = jax.tree_util.tree_map(jax.device_put, params,
                                        fsdp_param_shardings(model, mesh))
    state = init_state(params, server=comp.server, seed=args.seed, mesh=mesh)
    return cfg, model, mesh, step, state, comp


def batch_fn_for(cfg, args):
    stream = LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                            global_batch=args.batch, seed=args.seed)

    def fn(step_idx: int) -> dict:
        b = lm_batch(stream, step_idx)
        if cfg.input_kind != "tokens":
            rng = np.random.RandomState(step_idx)
            b["inputs"] = rng.randn(args.batch, args.seq_len, cfg.d_model).astype(np.float32) * 0.3
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.mrope:
            out["positions3"] = jnp.broadcast_to(
                out["positions"][..., None], out["positions"].shape + (3,))
        if args.tau > 1:
            out = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (args.tau,) + x.shape), out)
        return out

    return fn


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full config (TPU deployment)")
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--host-data", type=int, default=1)
    ap.add_argument("--host-model", type=int, default=1)
    ap.add_argument("--mode", default=None, choices=[None, "simple", "streamed"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--local-lr", type=float, default=1e-2)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--compressor", default="sparsign")
    ap.add_argument("--server", default="scaled_sign_ef")
    ap.add_argument("--vote-impl", default="psum",
                    choices=["psum", "hier", "allgather_packed"],
                    help="vote wire; allgather_packed engages the packed "
                         "uplinks (2-bit ternary, or pack8 for qsgd8)")
    ap.add_argument("--budget", type=float, default=1.0)
    ap.add_argument("--budget-kind", default="fixed",
                    choices=["fixed", "linf_share", "l2_norm",
                             "target_sparsity"],
                    help="budget semantics; target_sparsity doubles as the "
                         "golomb wire's plan-time nonzero fraction")
    ap.add_argument("--local-budget", type=float, default=10.0)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--quorum", type=int, default=1,
                    help="vote-server deadband: |votes| < quorum -> no step "
                         "(majority_vote only); under elastic participation "
                         "it is re-derived as the fraction quorum/M of "
                         "realized participation")
    ap.add_argument("--quorum-frac", type=float, default=None,
                    help="elastic quorum as an explicit fraction of realized "
                         "participation W (overrides the quorum/M "
                         "derivation); engages elastic participation")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-round report-dropout rate (chaos: crashed/"
                         "straggling reporters); engages elastic "
                         "participation")
    ap.add_argument("--worker-weights", default=None,
                    help="comma-separated per-worker vote weights (one per "
                         "worker, flat worker-index order); engages elastic "
                         "participation")
    ap.add_argument("--bucketed", action="store_true",
                    help="bucketized uplink (one collective per bucket; "
                         "streamed mode double-buffers exchange vs compute)")
    ap.add_argument("--ring", action="store_true",
                    help="ring-pipelined payload gather (allgather_packed "
                         "only): ppermute fixed-shape chunks around the "
                         "worker ring with streaming decode-sum — O(1) peak "
                         "HBM instead of O(M)")
    ap.add_argument("--ring-chunk-rows", type=int, default=None,
                    help="payload rows per ring chunk (multiple of 32; "
                         f"default {collectives.DEFAULT_RING_CHUNK_ROWS})")
    ap.add_argument("--backend", default=None, choices=BACKENDS,
                    help="compression kernel backend (default: pallas on a "
                         "TPU, jnp elsewhere; $REPRO_KERNEL_BACKEND)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--history-out", default=None)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, model, mesh, step, state, comp = build_everything(args)
    lcfg = loop_lib.LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                               ckpt_every=args.ckpt_every, fail_at_step=args.fail_at)
    with jax.sharding.set_mesh(mesh):
        state, history = loop_lib.run(step, state, batch_fn_for(cfg, args), lcfg)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    print(f"done: {len(history)} log points, final loss "
          f"{history[-1]['loss'] if history else float('nan'):.4f}")


if __name__ == "__main__":
    main()
