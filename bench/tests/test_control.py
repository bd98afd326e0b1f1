"""The control fails the comparison: the reference computed in float8 (the
precision below the configurations' bfloat16), put in the trainer's place,
against the float32 reference, under each chip cell's own limits.

On the chip this is read at the cells' own sizes by ``bench/calibrate.py``
(PERF.md gives the readings); here at a size a CPU holds: widths cut to 256,
eight mamba2 layers or two qwen layers, bfloat16 weights, 2 x 256 tokens."""

import json
import pathlib

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import harness
import small

BENCH = pathlib.Path(__file__).resolve().parents[1]
SIZES = {
    "mamba2-370m": (
        {"d_model": 256, "n_layer": 8, "vocab_size": 2048, "d_state": 64,
         "headdim": 64, "chunk_size": 32, "dtype": "bfloat16"}, {}),
    "qwen1.5-4b": (
        {"hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 2,
         "intermediate_size": 512, "vocab_size": 2048, "torch_dtype": "bfloat16"}, {}),
}
CELLS = [w for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS, ids=[w["name"] for w in CELLS])
@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_float8_control_fails_the_cells_limits(cell, seed):
    limits = json.loads((BENCH / "workloads" / f"{cell['name']}.json").read_text())["limits"]
    assert all(v is not None for v in limits.values())
    c = small.small_cell(cell["config"], workers=1, batch=2, seq=256, limits=limits)
    c.config.update(SIZES[cell["config"]][0])
    c.traffic["method"]["budget"] = 100.0   # leaves whose first update moves
                                            # 1,000 coordinates or more
    cmp = harness.bench_module("compare")
    gen = harness.traffic_gen(c, seed)
    dev = jax.devices()[0]
    p0 = harness.host_copy(harness.make_init(c, SingleDeviceSharding(dev))(
        harness.refalgo().param_key(seed)))
    want = harness.run_reference(c, gen, seed, dev, p0)
    control = harness.run_reference(c, gen, seed, dev, p0, precision="float8")
    compared = cmp.compare(harness.readings_of(control, c, dev),
                           harness.readings_of(want, c, dev), limits)
    assert not cmp.passed(compared), compared
