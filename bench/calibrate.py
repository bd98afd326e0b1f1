#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--faults half_batch,unchanged]

In one process, for each seed: the trainer's set-up steps against the
reference (the lower readings), and for the control seeds the reference in
float8 put in the trainer's place (the control), and each planted fault
likewise. One JSON line per seed and kind; no window is timed.
"""

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import harness

    cell, _ = harness.load_cell(args.workload)
    cmp = harness.bench_module("compare")
    devices = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    faults = [f for f in args.faults.split(",") if f]

    def emit(seed, kind, got, want, secs):
        print(json.dumps({"seed": seed, "kind": kind, "seconds": secs,
                          "readings": cmp.readings(got, want),
                          "leaves": cmp.leaf_table(got, want),
                          "losses": got.losses, "ref_losses": want.losses}),
              flush=True)

    def readings(snap):
        return harness.readings_of(snap, cell, devices[0])

    built = harness.build_step(cell, devices)
    refs = {"program": harness.make_reference(cell),
            "control_float8": harness.make_reference(cell, precision="float8")}
    refs.update((f, harness.make_reference(cell, fault=f)) for f in faults)
    for seed in args.seeds:
        t0 = time.perf_counter()
        prog = harness.Program(cell, devices, seed, built=built)
        gen = harness.traffic_gen(cell, seed)
        got = harness.run_setup(prog, gen, cell)
        p0 = prog.p0
        del prog
        t1 = time.perf_counter()
        want = readings(harness.run_reference(cell, gen, seed, devices[0], p0,
                                              ref=refs["program"]))
        t2 = time.perf_counter()
        emit(seed, "program", readings(got), want, [t1 - t0, t2 - t1])
        if seed in args.control_seeds:
            for kind in ["control_float8"] + faults:
                t3 = time.perf_counter()
                bad = harness.run_reference(cell, gen, seed, devices[0], p0,
                                            ref=refs[kind])
                emit(seed, kind, readings(bad), want, [time.perf_counter() - t3])
    stats = devices[0].memory_stats()
    print(json.dumps({"memory_stats": {k: int(v) for k, v in (stats or {}).items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
