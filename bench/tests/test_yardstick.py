"""The FLOP and byte counts behind mfu and the rooflines, against values
worked out by hand from the published shapes."""

import json
import pathlib

import jax
import pytest

import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_mamba2_flops_by_hand():
    # per token and layer: in-projections 2*1024*(2*2048 + 2*128 + 32)
    # = 8,978,432; out-projection 2*2048*1024 = 4,194,304; SSD with 128-token
    # chunks: C.B 2*128*128 = 32,768, diagonal blocks 2*32*128*64 = 524,288,
    # chunk states and off-diagonal outputs 2 * 2*32*64*128 = 1,048,576;
    # 48 layers = 709,361,664; tied head 2*1024*50288 = 102,989,824
    f = harness.load_module(BENCH / "flops" / "mamba2-370m.py")
    assert f.forward_per_token(config("mamba2-370m")) == 812_351_488
    assert f.train_flops(config("mamba2-370m"), batch=8, seq=2048) == \
        3 * 812_351_488 * 8 * 2048


def test_qwen_flops_by_hand():
    # per token and layer: q, k, v 2*2560*7680 = 39,321,600; o 2*2560*2560
    # = 13,107,200; SwiGLU 3*2*2560*6912 = 106,168,320; scores and values
    # over 2048 positions 2*2*2048*20*128 = 20,971,520; 12 layers =
    # 2,154,823,680; untied head 2*2560*151936 = 777,912,320
    f = harness.load_module(BENCH / "flops" / "qwen1.5-4b.py")
    assert f.forward_per_token(config("qwen1.5-4b"), 2048) == 2_932_736_000
    assert f.train_flops(config("qwen1.5-4b"), batch=4, seq=2048) == \
        3 * 2_932_736_000 * 4 * 2048


@pytest.mark.parametrize("name, params, f32_coords", [
    # 48 layers x 6,601,056 + 50288 x 1024 + 1024; A_log, D, dt_bias: 3 x 48 x 32
    ("mamba2-370m", 368_346_624, 4_608),
    # 12 layers x (4 x 2560^2 + 3 x 2560 + 3 x 2560 x 6912 + 2 x 2560 = 79,311,360)
    # + 2 x 151936 x 2560 + 2560
    ("qwen1.5-4b", 1_729_651_200, 0),
])
def test_roofline_bytes_by_hand(name, params, f32_coords):
    cfg = config(name)
    ref = harness.load_module(BENCH / "configs" / f"{name}.py")
    shapes = jax.eval_shape(lambda: ref.init_params(jax.random.key(0), cfg))
    leaves = [(x.size, x.dtype.itemsize) for x in jax.tree_util.tree_leaves(shapes)]
    assert sum(n for n, _ in leaves) == params
    assert sum(n for n, b in leaves if b == 4) == f32_coords
    bf16 = params - f32_coords
    # uplink: read the gradient, write 2 bits; server with M = 4 workers:
    # read four 2-bit payloads, read and write the parameter
    assert sum(n * (b + 0.25) for n, b in leaves) == 2.25 * bf16 + 4.25 * f32_coords
    assert sum(n * (0.25 * 4 + 2 * b) for n, b in leaves) == 5 * bf16 + 9 * f32_coords
