#!/usr/bin/env python3
"""Compile a cell for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--reference]

Builds the cell's train step on a data mesh of the described ``v5e:2x2``'s
first devices, compiles it from shapes alone, and prints its
``memory_analysis()`` and whether a Mosaic kernel (``tpu_custom_call``) is
in it; ``--reference`` compiles the reference's gradient of one worker on
one device as well. A rehearsal: it says what the compiler accepts and how
much memory one program asks for, never how fast anything runs.
"""

import argparse
import functools
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent


def describe(label, compiled):
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"[{label}] arguments {ma.argument_size_in_bytes / gib:.3f} GiB, "
          f"outputs {ma.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {ma.temp_size_in_bytes / gib:.3f} GiB, "
          f"aliased {ma.alias_size_in_bytes / gib:.3f} GiB; "
          f"tpu_custom_call: {'tpu_custom_call' in compiled.as_text()}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    import harness
    from repro.train.state import TrainState

    jax.config.update("jax_enable_compilation_cache", False)
    cell, _ = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = topo.devices[:cell.chips]
    model, mesh, step, comp = harness.build_step(cell, devices)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(functools.partial(cell.reference.init_params,
                                              cfg=cell.config), jax.random.key(0))
    harness.check_layout(params, model.param_shapes())
    shaped = lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    state = TrainState(params=jax.tree_util.tree_map(lambda x: shaped(x, rep), params),
                       ef_residual=None,
                       step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                       seed=jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep))
    rows, seq = cell.global_batch, int(cell.traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    batch = {"inputs": tok, "labels": tok, "positions": tok}
    print(f"[{cell.name}] {cell.chips} x {devices[0].device_kind}, mesh "
          f"{dict(mesh.shape)}, batch {rows} x {seq}", flush=True)
    with jax.sharding.set_mesh(mesh):
        describe("train step", step.lower(state, batch).compile())
    if args.reference:
        ra = harness.refalgo()
        ref = ra.Reference(cell.reference, cell.config, workers=cell.workers,
                           budget=1.0, lr=1e-3, warmup=0)
        one = SingleDeviceSharding(devices[0])
        b = int(cell.traffic["batch_per_worker"])
        wtok = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=one)
        u32 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one)
        describe("reference gradient, one worker", ref._grad.lower(
            jax.tree_util.tree_map(lambda x: shaped(x, one), params),
            {"inputs": wtok, "labels": wtok, "positions": wtok},
            u32, u32, u32).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
