"""The reduction by scope (``bench/scopes.py``): the innermost-scope rule,
name stacks read from a trace's HLO, and a cut of a trace recorded on four
TPU v5e chips."""

import gzip
import pathlib

import jax
import jax.numpy as jnp
import pytest

import harness

sc = harness.bench_module("scopes")
BENCH = pathlib.Path(__file__).resolve().parents[1]


def test_innermost_scope_wins():
    assert sc.layer_of("jit(train_step)/shard_map/exchange/server/pallas_call") == "server"
    assert sc.layer_of("jit(train_step)/fwd_bwd/transpose(jvp())/while/body/"
                       "closed_call/checkpoint/rematted_computation/mul") == "fwd_bwd"
    assert sc.layer_of("transpose(jvp(fwd_bwd))/transpose(jvp(mixer))/add") == "fwd_bwd"
    assert sc.layer_of("jit(train_step)/shard_map/xor") == "unscoped"
    assert sc.layer_of("") == "unscoped"


def test_name_stacks_from_the_traces_hlo(tmp_path):
    """A trace holds each program's HLO; its instructions' op_names carry
    the scopes the program was traced under."""
    @jax.jit
    def train_step(x):
        with jax.named_scope("uplink"):
            y = jnp.sin(x) @ x
        with jax.named_scope("counters"):
            return jnp.sum(y)

    x = jnp.ones((64, 64))
    train_step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        train_step(x).block_until_ready()
    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    names = sc.hlo_op_names(path.read_bytes())
    assert {sc.layer_of(stack) for stack in names.values()} >= {"uplink", "counters"}


CUT = BENCH / "tests" / "data" / "mamba2-370m.vote4.b1x512.exchange.xplane.pb.gz"


@pytest.fixture(scope="module")
def cut(tmp_path_factory):
    """24.5 ms of a step of mamba2-370m.vote4.b1x512 recorded on four TPU
    v5e: the uplink, the all-gathers and the server, cut by
    ``cut_scoped_trace.py`` with each operation's op_name as a stat."""
    path = tmp_path_factory.mktemp("trace") / "cut.xplane.pb"
    path.write_bytes(gzip.decompress(CUT.read_bytes()))
    return path


def _device_events(path):
    """Per device: (start, end, layer, line) of every event of the two
    operation lines inside the window of the host spans."""
    from jax.profiler import ProfileData

    tr = harness.bench_module("trace")
    pd = ProfileData.from_file(str(path))
    spans = tr.host_spans(pd)
    w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    out = []
    for plane in tr.device_planes(pd):
        evs = []
        for line in plane.lines:
            if line.name in (tr.OPS_LINE, tr.ASYNC_LINE):
                for ev in line.events:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e > s:
                        stack = dict(ev.stats).get("tf_op", "")
                        evs.append((s, e, sc.layer_of(stack), line.name))
        out.append(evs)
    return out


def _covered(points, intervals):
    """Length of the union of ``intervals``, by brute force over the
    elementary segments between ``points``."""
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in intervals))


def test_cut_of_a_four_chip_trace(cut):
    r = sc.reduce(cut, steps=1)
    assert r.devices == 4
    assert r.window_s == pytest.approx(0.0245, abs=1e-9)
    devices = _device_events(cut)
    ops = "XLA Ops"
    # the layers but forward and backward hold no nested event here: their
    # time is the sum of their events' durations, straight from the trace
    for layer in ("uplink", "exchange", "server", "counters"):
        total = sum(e - s for evs in devices for s, e, x, line in evs
                    if x == layer and line == ops) * 1e-9 / 4
        assert r.layer_s[layer] == pytest.approx(total, rel=1e-9), layer
    assert r.layer_s["uplink"] == pytest.approx(6.938451e-3, rel=1e-6)
    assert r.layer_s["exchange"] == pytest.approx(5.272181e-3, rel=1e-6)
    assert r.layer_s["server"] == pytest.approx(1.020212e-2, rel=1e-6)
    assert r.layer_s["counters"] == pytest.approx(3.779808e-4, rel=1e-6)
    # the five scopes and the unscoped time add up to the busy time
    assert sum(r.layer_s.values()) == pytest.approx(r.busy_s, rel=1e-9)
    assert r.layer_s["unscoped"] < 0.01 * r.busy_s
    # the exchange's exposed time: the part of its events that no event of
    # another layer on the same chip overlaps
    exch = exposed = 0.0
    for evs in devices:
        mine = [(s, e) for s, e, x, _ in evs if x == "exchange"]
        others = [(s, e) for s, e, x, line in evs if x != "exchange" and line == ops]
        points = sorted({p for iv in mine + others for p in iv})
        exch += _covered(points, mine)
        exposed += _covered(points, mine) - sum(
            b - a for a, b in zip(points, points[1:])
            if any(s <= a and b <= e for s, e in mine)
            and any(s <= a and b <= e for s, e in others))
    assert r.exchange_s == pytest.approx(exch * 1e-9 / 4, rel=1e-9)
    assert r.exchange_exposed_s == pytest.approx(exposed * 1e-9 / 4, rel=1e-9)
    assert 0 < r.exchange_exposed_s <= r.exchange_s
