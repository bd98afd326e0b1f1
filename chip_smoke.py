#!/usr/bin/env python3
"""Bring-up check of the training path on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the cross-chip vote exchange, four chips

One process drives every chip it uses. It exits non-zero, and prints no
result, when JAX finds no TPU; it never falls back to the CPU or to the jnp
kernel backend.

One chip, in order:

1. the device JAX reports (platform, kind, count);
2. kernel parity: the compiled Pallas kernels of the training path
   (sparsign, sparsign_pack2bit, unpack2bit_sum, vote_update,
   vote_update_packed2bit, ef_server) on the 50280x1024 mamba2-370m embedding
   leaf, bitwise against their jnp references;
3. train steps: mamba2-370m at its published widths and full depth, through
   ``repro.launch.train.build_everything`` and ``repro.train.loop.run`` with
   the pallas backend, batch 8 x 2048 tokens: 3 steps with the CLI defaults
   (sparsign, scaled_sign_ef, psum), then 3 with majority_vote over
   allgather_packed (the fused 2-bit uplink and the decode-sum at M=1). Each
   step's loss must be finite and no step after step 0 may compile.

``--chips 4`` runs only the cross-chip phase: the same model on a (4, 1)
data mesh, one step from the same state and batch with ``psum`` and one with
``allgather_packed`` (majority_vote). The two new parameter trees must be
bitwise equal, and the state must sit on all four devices.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
else to ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "mamba2-370m"
BATCH, SEQ_LEN = 8, 2048
STEPS = 3
KERNEL_WORKERS = 4   # gathered payloads in the unpack2bit_sum and
                     # vote_update_packed2bit parity cases
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def device_info(want_count: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if d.platform != "tpu":
        fail(f"no TPU found: JAX reports platform {d.platform!r}")
    if len(devices) != want_count:
        fail(f"expected {want_count} TPU device(s), JAX reports {len(devices)}")
    return info


def import_repro():
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import train
    except ModuleNotFoundError as e:
        fail(f"the repro package is not importable from {ROOT / 'src'}: {e}")
    return train


class CompileCounter:
    """Counts XLA backend compiles in this process."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def mismatches(a, b) -> int:
    """Count of elements whose bits differ (-1 on a shape/dtype mismatch)."""
    import jax
    import jax.numpy as jnp

    if a.shape != b.shape or a.dtype != b.dtype:
        return -1
    if jnp.issubdtype(a.dtype, jnp.floating):
        bits = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
        a = jax.lax.bitcast_convert_type(a, bits)
        b = jax.lax.bitcast_convert_type(b, bits)
    return int(jnp.sum(a != b))


def tree_mismatches(ta, tb) -> int:
    import jax

    la, lb = jax.tree_util.tree_leaves(ta), jax.tree_util.tree_leaves(tb)
    if len(la) != len(lb):
        return -1
    counts = [mismatches(a, b) for a, b in zip(la, lb)]
    return -1 if -1 in counts else sum(counts)


def kernel_parity(cfg) -> None:
    """Each compiled kernel against its jnp reference on one real-width leaf."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import common
    from repro.kernels.ef_server.ops import ef_server_op
    from repro.kernels.ef_server.ref import ef_scale, ef_server_ref
    from repro.kernels.pack2bit.ops import unpack2bit_sum_op
    from repro.kernels.pack2bit.ref import unpack2bit_sum_ref
    from repro.kernels.sparsign.ops import sparsign_op
    from repro.kernels.sparsign.ref import sparsign_ref
    from repro.kernels.sparsign_pack2bit.ops import sparsign_pack2bit_op
    from repro.kernels.sparsign_pack2bit.ref import sparsign_pack2bit_ref
    from repro.kernels.vote_update.ops import (vote_update_op,
                                               vote_update_packed2bit_op)
    from repro.kernels.vote_update.ref import (vote_update_packed2bit_ref,
                                               vote_update_ref)

    shape = (cfg.vocab_size, cfg.d_model)
    n = math.prod(shape)
    dt = cfg.activation_dtype
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    g = (jax.random.normal(k[0], shape, jnp.float32) * 0.5).astype(dt)
    w = (jax.random.normal(k[1], shape, jnp.float32) * 0.02).astype(dt)
    e = jax.random.normal(k[2], shape, jnp.float32) * 0.01
    budget, seed, base = jnp.float32(1.0), jnp.uint32(1234), jnp.uint32(77)
    gathered = jnp.stack([sparsign_pack2bit_ref(g, budget, seed + i, base)
                          for i in range(KERNEL_WORKERS)])
    votes = jnp.sum(jnp.stack([sparsign_ref(g, budget, seed + i, base)
                               for i in range(KERNEL_WORKERS)]).astype(jnp.int32),
                    axis=0)
    d = votes.astype(jnp.float32) / KERNEL_WORKERS
    scale = ef_scale(d, e)
    eta = jnp.float32(3e-3)

    # name: (kernel, reference, operands)
    cases = {
        "sparsign": (lambda *a: sparsign_op(*a, interpret=False), sparsign_ref,
                     (g, budget, seed, base)),
        "sparsign_pack2bit": (lambda *a: sparsign_pack2bit_op(*a, interpret=False),
                              sparsign_pack2bit_ref, (g, budget, seed, base)),
        "unpack2bit_sum": (
            lambda x: unpack2bit_sum_op(x, n, shape, interpret=False),
            lambda x: common.from_2d(unpack2bit_sum_ref(x), n, shape), (gathered,)),
        "vote_update": (
            lambda *a: vote_update_op(*a, quorum=2, interpret=False),
            lambda *a: vote_update_ref(*a, quorum=2), (w, votes, eta)),
        "vote_update_packed2bit": (
            lambda *a: vote_update_packed2bit_op(*a, quorum=2, interpret=False),
            lambda *a: vote_update_packed2bit_ref(*a, quorum=2),
            (w, gathered, eta)),
        "ef_server": (lambda *a: ef_server_op(*a, interpret=False), ef_server_ref,
                      (d, e, scale)),
    }
    print(f"[kernels] leaf {shape} {jnp.dtype(dt).name}, "
          f"{KERNEL_WORKERS}-worker gather for unpack2bit_sum and "
          f"vote_update_packed2bit", flush=True)
    for name, (kernel, ref, operands) in cases.items():
        kernel = jax.jit(kernel)
        if "tpu_custom_call" not in kernel.lower(*operands).as_text():
            fail(f"kernel {name}: no tpu_custom_call in its lowered program")
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel(*operands))
        secs = time.perf_counter() - t0
        bad = tree_mismatches(got, jax.jit(ref)(*operands))
        print(f"[kernels] {name}: mismatches={bad} "
              f"(first call incl. compile {secs:.3f}s)", flush=True)
        if bad != 0:
            fail(f"kernel {name} differs from its jnp reference "
                 f"({bad} elements, -1 = shape/dtype)")
    print(f"[kernels] parity passed: {len(cases)} kernels bitwise equal to "
          f"their references", flush=True)


def train_argv(*extra: str) -> list:
    return ["--arch", ARCH, "--full", "--batch", str(BATCH),
            "--seq-len", str(SEQ_LEN), "--backend", "pallas", *extra]


def describe(cfg, state) -> str:
    import jax

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"vocab {cfg.vocab_size}, ssm_state {cfg.ssm_state}, "
            f"{n_params} params ({jax.tree_util.tree_leaves(state.params)[0].dtype})")


def train_phase(train, counter: CompileCounter, label: str, *extra: str) -> None:
    import jax

    from repro.train import loop as loop_lib

    args = train.build_parser().parse_args(train_argv("--steps", str(STEPS), *extra))
    t0 = time.perf_counter()
    cfg, model, mesh, step, state, comp = train.build_everything(args)
    batch_fn = train.batch_fn_for(cfg, args)
    print(f"[train:{label}] {describe(cfg, state)}; mesh {dict(mesh.shape)}; "
          f"compressor={comp.compressor} server={comp.server} "
          f"vote_impl={args.vote_impl} backend={args.backend}; "
          f"batch {BATCH} x {SEQ_LEN}", flush=True)
    with jax.sharding.set_mesh(mesh):
        hlo = step.lower(state, batch_fn(0)).as_text()
    if "tpu_custom_call" not in hlo:
        fail(f"train:{label}: the lowered step has no tpu_custom_call")
    print(f"[train:{label}] build + lower {time.perf_counter() - t0:.1f}s; "
          f"lowered step contains tpu_custom_call", flush=True)

    records = []

    def timed_step(s, b):
        t = time.perf_counter()
        out = jax.block_until_ready(step(s, b))
        records.append((time.perf_counter() - t, counter.n))
        return out

    lcfg = loop_lib.LoopConfig(total_steps=STEPS, log_every=1)
    with jax.sharding.set_mesh(mesh):
        state, history = loop_lib.run(timed_step, state, batch_fn, lcfg,
                                      log=lambda line: print(f"[train:{label}] {line}"))
    for (secs, _), h in zip(records, history):
        kind = "set-up: compile + step" if h["step"] == 0 else "step"
        print(f"[train:{label}] step {h['step']}: loss={h['loss']!r} "
              f"{kind} {secs:.3f}s", flush=True)
    compiles_after_0 = records[-1][1] - records[0][1]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[train:{label}] compiles after step 0: {compiles_after_0}; "
          f"peak_bytes_in_use (process so far)={stats.get('peak_bytes_in_use')}",
          flush=True)
    losses = [h["loss"] for h in history]
    if len(losses) != STEPS or not all(math.isfinite(x) for x in losses):
        fail(f"train:{label}: losses {losses} are not {STEPS} finite values")
    if compiles_after_0 != 0:
        fail(f"train:{label}: {compiles_after_0} compiles after step 0")


def four_chip_phase(train) -> None:
    """psum vs allgather_packed majority vote on a (4, 1) data mesh."""
    import jax

    devices = set(jax.devices())
    params = {}
    for impl in ("psum", "allgather_packed"):
        args = train.build_parser().parse_args(train_argv(
            "--steps", "1", "--host-data", "4", "--server", "majority_vote",
            "--vote-impl", impl))
        cfg, model, mesh, step, state, comp = train.build_everything(args)
        batch = train.batch_fn_for(cfg, args)(0)
        placed = {d for x in jax.tree_util.tree_leaves(state)
                  for d in x.sharding.device_set}
        if placed != devices:
            fail(f"{impl}: the state sits on {len(placed)} of 4 devices")
        if params:   # same seed: the second build must start where the first did
            start_bad = tree_mismatches(params["start"], state.params)
            if start_bad != 0:
                fail(f"{impl}: initial parameters differ ({start_bad})")
        else:
            params["start"] = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
        t0 = time.perf_counter()
        with jax.sharding.set_mesh(mesh):
            new_state, metrics = jax.block_until_ready(step(state, batch))
        secs = time.perf_counter() - t0
        loss = float(metrics["loss"])
        shards = {s.device for x in jax.tree_util.tree_leaves(new_state.params)
                  for s in x.addressable_shards}
        print(f"[chips4:{impl}] {describe(cfg, new_state)}; mesh {dict(mesh.shape)}; "
              f"loss={loss!r}; wire_bytes_per_device="
              f"{float(metrics['wire_bytes_per_device'])!r}; "
              f"set-up: compile + step {secs:.3f}s; "
              f"new params on {len(shards)} devices", flush=True)
        if not math.isfinite(loss):
            fail(f"{impl}: loss {loss!r} is not finite")
        if shards != devices:
            fail(f"{impl}: new parameters sit on {len(shards)} of 4 devices")
        params[impl] = new_state.params
    bad = tree_mismatches(params["psum"], params["allgather_packed"])
    print(f"[chips4] psum vs allgather_packed parameters: mismatches={bad}",
          flush=True)
    if bad != 0:
        fail(f"psum and allgather_packed parameters differ ({bad} elements)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    info = device_info(args.chips)
    train = import_repro()
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[setup] compilation cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    if args.chips == 4:
        four_chip_phase(train)
    else:
        kernel_parity(get_config(ARCH))
        train_phase(train, counter, "psum")
        train_phase(train, counter, "allgather_packed", "--server", "majority_vote",
                    "--vote-impl", "allgather_packed")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
