"""The trace reduction, on hand-made intervals and on a cut of a trace
recorded on one TPU v5e (mamba2-370m.b8x2048, the last 31 ms of a step and
the first 9 of the next: the cut holds one step's sixteen uplink and
thirty-two server kernels)."""

import gzip
import pathlib

import pytest

import harness

tr = harness.bench_module("trace")
BENCH = pathlib.Path(__file__).resolve().parents[1]
CUT = BENCH / "tests" / "data" / "mamba2-370m.b8x2048.step-end.xplane.pb.gz"


def test_self_times_take_nested_events_out():
    # a loop [0, 10) holding [1, 3) and [4, 9), which holds [5, 6)
    got = {n: t for _, _, n, t in tr.self_times(
        [(0, 10, "loop"), (1, 3, "a"), (4, 9, "b"), (5, 6, "c"), (12, 13, "d")])}
    assert got == {"loop": 3, "a": 2, "b": 4, "c": 1, "d": 1}


def test_merge_and_uncovered():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert tr.uncovered((2, 6), merged) == 2      # [3, 5) is uncovered
    assert tr.uncovered((10, 12), merged) == 2


def test_classify_by_order_and_default():
    layers = tr.load_layers(BENCH / "layers")
    assert tr.classify("%sparsign_pack2bit_2d.16", layers).key == "uplink"
    assert tr.classify("%unpack2bit_sum_2d.3", layers).key == "server"
    assert tr.classify("%vote_update_2d.19", layers).key == "server"
    assert tr.classify("%all-gather-start.2", layers).key == "exchange"
    assert tr.classify("%fusion.773", layers).key == "fwd_bwd"


@pytest.fixture(scope="module")
def cut(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "cut.xplane.pb"
    path.write_bytes(gzip.decompress(CUT.read_bytes()))
    return path


def test_cut_of_a_chip_trace(cut):
    from jax.profiler import ProfileData

    r = tr.reduce(cut, tr.load_layers(BENCH / "layers"), steps=1)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.040, abs=1e-9)
    assert 0 < r.busy_s <= r.window_s
    # the kernels, summed straight from the trace's events
    pd = ProfileData.from_file(str(cut))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(line for line in plane.lines if line.name == "XLA Ops")
    total = {"uplink": 0.0, "server": 0.0}
    count = {"uplink": 0, "server": 0}
    for ev in ops.events:
        key = ("uplink" if ev.name.startswith("%sparsign")
               else "server" if ev.name.startswith(("%unpack2bit", "%vote_update"))
               else None)
        if key:
            total[key] += ev.duration_ns * 1e-9
            count[key] += 1
    assert count == {"uplink": 16, "server": 32}
    assert r.layer_s["uplink"] == pytest.approx(total["uplink"], rel=1e-9)
    assert r.layer_s["server"] == pytest.approx(total["server"], rel=1e-9)
    assert r.layer_s["uplink"] == pytest.approx(4.772839e-3, rel=1e-6)
    assert r.layer_s["server"] == pytest.approx(5.078343e-3, rel=1e-6)
    # self times add up to the device's busy time (one line of operations)
    assert sum(r.layer_s.values()) == pytest.approx(r.busy_s, rel=1e-6)
    assert r.exposed_s == {"exchange": 0.0}
    assert len(r.breakdown["device_ops"]) == 10
    assert r.breakdown["device_ops"][0][0].startswith("%sparsign_pack2bit_2d")
