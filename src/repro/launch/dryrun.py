import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (device count is now locked at 512) ---
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config, trainer_mode
from repro.configs.shapes import SHAPES, applicable
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.dist.sharding import tp_param_shardings
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh, worker_axes_of
from repro.models.model import Model
from repro.serve.decode import build_decode_step, build_prefill, serve_input_specs
from repro.train.state import LrSchedule, TrainState
from repro.train.step_simple import TrainStepConfig, build_train_step
from repro.train.step_streamed import (StreamedStepConfig, build_fsdp_layout,
                                       build_streamed_train_step)

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, compiles,
and fits — and extract the roofline inputs from the compiled artifact.

Per cell we compile up to three variants:
  depth=full  -> memory_analysis (fits?), HLO collective census, compile proof
  depth=1,2   -> cost_analysis + wire-byte parse, linearly extrapolated in the
                 superblock repeat count R (exact for scan-structured programs;
                 XLA's cost analysis counts while bodies once — measured 8x
                 undercount on an 8-iteration scan, see EXPERIMENTS.md).
"""


def _compression(args) -> CompressionConfig:
    return CompressionConfig(
        compressor=args.compressor,
        budget=BudgetConfig(kind="fixed", value=args.budget),
        server=args.server,
        local_steps=args.tau,
        local_budget=args.local_budget,
        vote_dtype="int8",
    )


def _reduced(cfg: ModelConfig, depth: int) -> ModelConfig:
    n = len(cfg.pattern) * depth + len(cfg.tail_pattern)
    return dataclasses.replace(cfg, n_layers=n)


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input (no allocation)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, shape, mesh, worker_axes, tau: int = 1):
    wa = tuple(worker_axes) if len(worker_axes) > 1 else worker_axes[0]
    b, s = shape.global_batch, shape.seq_len
    lead = () if tau == 1 else (tau,)
    bspec = P(wa) if tau == 1 else P(None, wa)
    sh = NamedSharding(mesh, bspec)
    if cfg.input_kind == "tokens":
        inputs = jax.ShapeDtypeStruct(lead + (b, s), jnp.int32, sharding=sh)
    else:
        inputs = jax.ShapeDtypeStruct(lead + (b, s, cfg.d_model), cfg.activation_dtype, sharding=sh)
    batch = {
        "inputs": inputs,
        "labels": jax.ShapeDtypeStruct(lead + (b, s), jnp.int32, sharding=sh),
        "positions": jax.ShapeDtypeStruct(lead + (b, s), jnp.int32, sharding=sh),
    }
    if cfg.mrope:
        batch["positions3"] = jax.ShapeDtypeStruct(lead + (b, s, 3), jnp.int32, sharding=sh)
    return batch


def train_state_specs(cfg: ModelConfig, mesh, mode: str, server: str, fsdp_axis="data"):
    model = Model(cfg)
    shapes = model.param_shapes()
    if mode == "simple":
        param_sh = tp_param_shardings(model, mesh)
    else:
        # streamed: FSDP over data + TP over model, merged per leaf
        from repro.train.step_streamed import streamed_shardings
        param_sh, _, _ = streamed_shardings(model, mesh, fsdp_axis)

    params_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, param_sh)
    ef_sds = None
    if server == "scaled_sign_ef":
        ef_sds = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), params_sds)
    repl = NamedSharding(mesh, P())
    return TrainState(
        params=params_sds,
        ef_residual=ef_sds,
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        seed=jax.ShapeDtypeStruct((), jnp.uint32, sharding=repl),
    )


def input_specs(arch: str, shape_name: str, mesh, *, mode=None, comp=None, tau=1):
    """ShapeDtypeStruct stand-ins for every input of the cell's step function."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mode = mode or trainer_mode(arch)
    wa = worker_axes_of(mesh)
    if shape.kind == "train":
        state = train_state_specs(cfg, mesh, mode, comp.server if comp else "scaled_sign_ef")
        batch = train_batch_specs(cfg, shape, mesh, wa, tau=tau)
        return (state, batch)
    if shape.kind == "prefill":
        model = Model(cfg)
        psh = tp_param_shardings(model, mesh)
        params = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            model.param_shapes(), psh)
        batch = train_batch_specs(cfg, shape, mesh, wa)
        return (params, batch)
    # decode
    shard_seq = shape.global_batch < len(mesh.devices.flatten()) // mesh.shape["model"]
    return serve_input_specs(cfg, shape, mesh=mesh, worker_axes=wa, shard_seq=shard_seq)


def build_step(arch: str, shape_name: str, mesh, *, mode=None, comp=None,
               vote_impl="psum", cfg_override=None, pure_dp=False):
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mode = mode or trainer_mode(arch)
    model = Model(cfg)
    wa = tuple(mesh.axis_names) if pure_dp else worker_axes_of(mesh)
    if shape.kind == "train":
        if mode == "simple":
            return build_train_step(model, TrainStepConfig(
                compression=comp, lr=LrSchedule(base=1e-2), worker_axes=wa,
                vote_impl=vote_impl, donate=True), mesh)
        return build_streamed_train_step(model, StreamedStepConfig(
            compression=comp, lr=LrSchedule(base=1e-2), worker_axes=wa,
            fsdp_axis="data", donate=True), mesh)
    if shape.kind == "prefill":
        return build_prefill(model, mesh, worker_axes=wa)
    shard_seq = shape.global_batch < len(mesh.devices.flatten()) // mesh.shape["model"]
    return build_decode_step(model, mesh, worker_axes=wa, shard_seq=shard_seq)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool, args) -> dict:
    cfg_full = get_config(arch)
    shape = SHAPES[shape_name]
    runs, reason = applicable(cfg_full, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": trainer_mode(arch) if shape.kind == "train" else shape.kind,
        "compressor": args.compressor if shape.kind == "train" else None,
        "server": args.server if shape.kind == "train" else None,
        "vote_impl": args.vote_impl if shape.kind == "train" else None,
        "tau": args.tau if shape.kind == "train" else None,
        "status": "skip" if not runs else None,
        "skip_reason": reason or None,
    }
    if not runs:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    comp = _compression(args)
    mode = trainer_mode(arch)
    if getattr(args, "remat_policy", "full") != "full":
        cfg_full = dataclasses.replace(cfg_full, remat_policy=args.remat_policy)
        rec["remat_policy"] = args.remat_policy
    if mode == "streamed" and shape.kind == "train" and comp.server == "scaled_sign_ef":
        # fp32 server-EF residual for >=72B models cannot fit HBM next to the
        # params; streamed cells run Alg. 1 (SPARSIGNSGD, majority vote), which
        # is the paper's base method. Documented in EXPERIMENTS.md §Dry-run.
        comp = dataclasses.replace(comp, server="majority_vote")
        rec["server"] = "majority_vote (auto: EF residual infeasible at this scale)"
    depths = [None] if args.no_extrapolate else [None, 1, 2]
    per_depth = {}
    try:
        pure_dp = getattr(args, "pure_dp", False)
        for depth in depths:
            cfg = cfg_full if depth is None else _reduced(cfg_full, depth)
            t0 = time.time()
            step = build_step(arch, shape_name, mesh, mode=mode, comp=comp,
                              vote_impl=args.vote_impl, cfg_override=cfg,
                              pure_dp=pure_dp)
            with jax.sharding.set_mesh(mesh):
                specs = input_specs_with_cfg(cfg, shape_name, mesh, mode=mode, comp=comp,
                                             tau=args.tau, pure_dp=pure_dp)
                lowered = step.lower(*specs)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
            entry = {"lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1)}
            ca = compiled.cost_analysis() or {}
            entry["flops"] = float(ca.get("flops", 0.0))
            entry["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
            text = compiled.as_text()
            coll = hlo_stats.parse_collectives(text)
            entry["collectives"] = coll.as_dict()
            entry["op_census"] = hlo_stats.op_census(text)
            if depth is None:
                ma = compiled.memory_analysis()
                entry["memory"] = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                    "generated_code_bytes": int(ma.generated_code_size_in_bytes),
                }
            per_depth["full" if depth is None else str(depth)] = entry
            del step, lowered, compiled, text
        rec["status"] = "ok"
        rec["n_repeats"] = cfg_full.n_repeats
        rec["depths"] = per_depth
        if not args.no_extrapolate:
            rec["extrapolated"] = extrapolate(per_depth, cfg_full.n_repeats)
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug; record it
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def input_specs_with_cfg(cfg, shape_name, mesh, *, mode, comp, tau=1, pure_dp=False):
    """input_specs but honoring a depth-reduced config."""
    shape = SHAPES[shape_name]
    wa = tuple(mesh.axis_names) if pure_dp else worker_axes_of(mesh)
    if shape.kind == "train":
        if pure_dp:
            # every axis is a worker: params fully replicated
            from jax.sharding import NamedSharding
            model = Model(cfg)
            repl = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                  sharding=NamedSharding(mesh, P()))
            params_sds = jax.tree_util.tree_map(repl, model.param_shapes())
            ef_sds = None
            if comp.server == "scaled_sign_ef":
                ef_sds = jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding),
                    params_sds)
            rs = NamedSharding(mesh, P())
            state = TrainState(params=params_sds, ef_residual=ef_sds,
                               step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rs),
                               seed=jax.ShapeDtypeStruct((), jnp.uint32, sharding=rs))
        else:
            state = train_state_specs(cfg, mesh, mode, comp.server)
        batch = train_batch_specs(cfg, shape, mesh, wa, tau=tau)
        return (state, batch)
    if shape.kind == "prefill":
        model = Model(cfg)
        psh = tp_param_shardings(model, mesh)
        params = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            model.param_shapes(), psh)
        batch = train_batch_specs(cfg, shape, mesh, wa)
        return (params, batch)
    shard_seq = shape.global_batch < len(mesh.devices.flatten()) // mesh.shape["model"]
    return serve_input_specs(cfg, shape, mesh=mesh, worker_axes=wa, shard_seq=shard_seq)


def extrapolate(per_depth: dict, r_full: int) -> dict:
    """X(R) = X(1) + (X(2) - X(1)) * (R - 1), per metric."""
    d1, d2 = per_depth.get("1"), per_depth.get("2")
    if not d1 or not d2:
        return {}
    out = {}
    for key in ("flops", "bytes_accessed"):
        out[key] = d1[key] + (d2[key] - d1[key]) * (r_full - 1)
    w1 = d1["collectives"]["wire_bytes"]
    w2 = d2["collectives"]["wire_bytes"]
    out["collective_wire_bytes"] = w1 + (w2 - w1) * (r_full - 1)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all", choices=["all"] + ARCH_IDS)
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--compressor", default="sparsign")
    ap.add_argument("--server", default="scaled_sign_ef",
                    choices=["majority_vote", "scaled_sign_ef", "mean"])
    ap.add_argument("--budget", type=float, default=1.0)
    ap.add_argument("--local-budget", type=float, default=10.0)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--vote-impl", default="psum", choices=["psum", "hier", "allgather_packed"])
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--pure-dp", action="store_true",
                    help="treat EVERY mesh axis as a worker axis (sub-1B models: "
                         "kills TP/SP collectives; the vote is M-invariant)")
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                print(f"=== {arch} x {shape_name} x {'2x16x16' if mp else '16x16'} ===",
                      flush=True)
                rec = run_cell(arch, shape_name, multi_pod=mp, args=args)
                records.append(rec)
                status = rec["status"]
                extra = rec.get("skip_reason") or rec.get("error") or ""
                if status == "ok":
                    full = rec["depths"]["full"]
                    mem = full.get("memory", {})
                    print(f"  ok: compile={full['compile_s']}s "
                          f"args={mem.get('argument_bytes', 0)/2**30:.1f}GiB "
                          f"temp={mem.get('temp_bytes', 0)/2**30:.1f}GiB "
                          f"colls={full['collectives']['counts']}", flush=True)
                else:
                    print(f"  {status}: {extra[:300]}", flush=True)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(records, f, indent=1)
    ok = sum(r["status"] == "ok" for r in records)
    skip = sum(r["status"] == "skip" for r in records)
    fail = sum(r["status"] == "fail" for r in records)
    print(f"\n==== dry-run summary: {ok} ok / {skip} skip / {fail} fail ====")
    return 1 if fail else 0


if __name__ == "__main__":
    sys.exit(main())
