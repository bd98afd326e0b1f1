"""Compile the TPU path for a described v5e, without a chip.

Interpret mode cannot show what Mosaic refuses (casts, SMEM bitcasts, 8-bit
compares, relayouts, auto-partitioned kernels); compiling with
``interpret=False`` for a described ``v5e:2x2`` topology does. Every kernel
that compiles is pinned here at real width (the 50280x1024 mamba2-370m
embedding leaf, a 4-worker gather for the decode-sums and the fused decode
+ update), plus a whole train step of each wire ``chip_smoke.py`` drives, at
smoke size. The Golomb kernels have no Mosaic lowering yet and are left out
(ROADMAP S2).

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and it keeps it until it exits.
"""

import importlib.util
import json
import math
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

LEAF = (50280, 1024)
WORKERS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, one_chip):
    """(fn, operand shapes) of one kernel entry point with interpret=False."""
    import repro.core  # noqa: F401 — import order: the kernels' ops import core
    from repro.kernels import common
    from repro.kernels.ef_server.ops import ef_server_op
    from repro.kernels.pack2bit.ops import (pack2bit_op, unpack2bit_op,
                                            unpack2bit_sum_op, unpack2bit_wsum_op)
    from repro.kernels.pack8.ops import qsgd8_pack8_op, unpack8_sum_op
    from repro.kernels.sparsign.ops import sparsign_op
    from repro.kernels.sparsign_pack2bit.ops import sparsign_pack2bit_op
    from repro.kernels.ternary import ops as ternary
    from repro.kernels.vote_update.ops import (vote_update_op,
                                               vote_update_packed2bit_op,
                                               weighted_vote_update_op)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = math.prod(LEAF)
    rows = common.canonical_rows(n)
    f32, bf16 = jnp.float32, jnp.bfloat16
    g, g16, scalar, seed = s(LEAF, f32), s(LEAF, bf16), s((), f32), s((), jnp.uint32)
    packed = s((WORKERS, rows, common.LANES // 4), jnp.uint8)
    weights = s((WORKERS,), f32)

    def compressor(op, grad=g):
        return (lambda x, p, sd: op(x, p, sd, 0, interpret=False)), (grad, scalar, seed)

    return {
        "sparsign": compressor(sparsign_op),
        "sparsign_bf16": compressor(sparsign_op, g16),
        "sparsign_pack2bit": compressor(sparsign_pack2bit_op),
        "sign_pack2bit": compressor(ternary.sign_pack2bit_op),
        "noisy_sign_pack2bit": compressor(ternary.noisy_sign_pack2bit_op),
        "stochastic_ternary": compressor(ternary.stochastic_ternary_op),
        "qsgd8_pack8": compressor(qsgd8_pack8_op),
        "pack2bit": (lambda t: pack2bit_op(t, interpret=False), (s(LEAF, jnp.int8),)),
        "unpack2bit": (lambda p: unpack2bit_op(p, n, LEAF, interpret=False),
                       (s((rows, common.LANES // 4), jnp.uint8),)),
        "unpack2bit_sum": (lambda p: unpack2bit_sum_op(p, n, LEAF, interpret=False),
                           (packed,)),
        "unpack2bit_wsum": (
            lambda p, w: unpack2bit_wsum_op(p, w, n, LEAF, interpret=False),
            (packed, weights)),
        "unpack8_sum": (
            lambda p, w: unpack8_sum_op(p, w, n, LEAF, interpret=False),
            (s((WORKERS, rows, common.LANES), jnp.int8), weights)),
        "vote_update": (
            lambda w, v, e: vote_update_op(w, v, e, quorum=1, interpret=False),
            (g16, s(LEAF, jnp.int8), scalar)),
        "vote_update_packed2bit": (
            lambda w, p, e: vote_update_packed2bit_op(w, p, e, quorum=1,
                                                      interpret=False),
            (g16, packed, scalar)),
        "weighted_vote_update": (
            lambda w, v, t, e: weighted_vote_update_op(w, v, t, e, q_frac=0.5,
                                                       interpret=False),
            (g16, g, scalar, scalar)),
        "ef_server": (lambda d, e: ef_server_op(d, e, interpret=False), (g, g)),
    }[name]


KERNELS = ["sparsign", "sparsign_bf16", "sparsign_pack2bit", "sign_pack2bit",
           "noisy_sign_pack2bit", "stochastic_ternary", "qsgd8_pack8",
           "pack2bit", "unpack2bit", "unpack2bit_sum", "unpack2bit_wsum",
           "unpack8_sum", "vote_update", "vote_update_packed2bit",
           "weighted_vote_update", "ef_server"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*operands).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _simple_step(mesh, backend, *, server="majority_vote", vote_impl="allgather_packed",
                 **step_kw):
    """(step, state shapes, batch shapes) of the simple trainer on the smoke
    mamba2-370m, the batch split over the data axis; ``step_kw`` goes to
    ``TrainStepConfig``."""
    from repro.configs.registry import get_config
    from repro.core.algorithm import CompressionConfig
    from repro.models.model import Model
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step

    model = Model(get_config("mamba2-370m", smoke=True))
    comp = CompressionConfig(compressor="sparsign", server=server)
    step = build_train_step(model, TrainStepConfig(
        compression=comp, lr=LrSchedule(base=1e-3), vote_impl=vote_impl,
        backend=backend, **step_kw), mesh)
    state = jax.eval_shape(lambda: init_state(model.init(jax.random.PRNGKey(0)),
                                              server=server, seed=0))
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    rows = 8 * mesh.shape["data"]
    batch = {k: jax.ShapeDtypeStruct((rows, 64), jnp.int32,
                                     sharding=NamedSharding(mesh, P("data")))
             for k in ("inputs", "labels", "positions")}
    return step, state, batch


@pytest.mark.parametrize("server,vote_impl", [("scaled_sign_ef", "psum"),
                                              ("majority_vote", "allgather_packed")])
def test_train_step_compiles_for_v5e(server, vote_impl, topo):
    """The simple trainer's whole step with the pallas backend on a (1, 1)
    mesh: the kernels sit inside the step's shard_map, where an axis left to
    GSPMD would make Mosaic refuse them."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step, state, batch = _simple_step(mesh, "pallas", server=server, vote_impl=vote_impl)
    with jax.sharding.set_mesh(mesh):
        compiled = step.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The layer scopes the benchmark reads its per-layer device time by
# ---------------------------------------------------------------------------

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _bench_scopes():
    """``bench/scopes.py``: the rule that gives an operation its layer."""
    spec = importlib.util.spec_from_file_location("bench_scopes",
                                                  ROOT / "bench" / "scopes.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _kernel_patterns():
    """{layer key: compiled name patterns} of ``bench/layers/*.json``."""
    out = {}
    for f in sorted((ROOT / "bench" / "layers").glob("*.json")):
        out[f.stem] = [re.compile(p) for p in json.loads(f.read_text()).get("patterns", [])]
    return out


def _instructions(hlo_text):
    """(name, opcode, op_name) of every instruction of a compiled HLO text."""
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = \S+ ([\w-]+)\(", line)
        if m:
            meta = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), meta.group(1) if meta else ""))
    return out


@pytest.mark.parametrize("workers", [1, 4])
def test_train_step_scopes_on_v5e(workers, topo):
    """Compiled for a v5e, every kernel and collective of the simple
    trainer's step sits under its layer's scope, and the kernels keep the
    instruction names the benchmark's name patterns find. Over the
    monolithic gather each leaf's server is the one fused decode + update
    kernel, and the exchange scope holds the all-gather alone."""
    from repro.launch.mesh import make_mesh

    layer_of = _bench_scopes().layer_of
    mesh = make_mesh((workers, 1), ("data", "model"), devices=topo.devices[:workers])
    step, state, batch = _simple_step(mesh, "pallas")
    with jax.sharding.set_mesh(mesh):
        ins = _instructions(step.lower(state, batch).compile().as_text())
    patterns = _kernel_patterns()
    seen = {"uplink": 0, "server": 0, "exchange": 0, "counters": 0}
    for name, opcode, op_name in ins:
        layer = layer_of(op_name)
        if opcode == "custom-call" and "pallas_call" in op_name:
            want = "uplink" if name.startswith("%sparsign") else "server"
            assert name.startswith(("%sparsign", "%unpack2bit", "%vote_update")), name
            assert layer == want, (name, op_name)
            assert any(p.search(name) for p in patterns[want]), name
            seen[want] += 1
            if want == "server":
                assert name.startswith("%vote_update_packed2bit"), name
        elif opcode in ("all-gather", "all-gather-start"):
            assert layer == "exchange", (name, op_name)
            seen["exchange"] += 1
        elif "reduce" in name and layer == "counters":
            seen["counters"] += 1
    leaves = 16   # the smoke mamba2's parameter leaves
    assert seen["uplink"] == leaves and seen["server"] == leaves
    exchange_ops = {opcode for _, opcode, op_name in ins
                    if layer_of(op_name) == "exchange"}
    assert exchange_ops <= {"all-gather", "all-gather-start", "all-gather-done"}, \
        exchange_ops
    assert seen["exchange"] == (leaves if workers > 1 else 0)
    assert seen["counters"] >= 1   # the nonzero count over the payloads


def test_innermost_scope_rule():
    scopes = _bench_scopes()
    cases = {
        "jit(train_step)/shard_map/fwd_bwd/transpose(jvp())/while/body/closed_call/"
        "checkpoint/rematted_computation/dot_general": "fwd_bwd",
        "transpose(jvp(fwd_bwd))/transpose(jvp(mixer))/mul": "fwd_bwd",
        "jit(train_step)/exchange/server/jit(unpack2bit_sum_op)/pallas_call": "server",
        "jit(train_step)/shard_map/exchange/all_gather": "exchange",
        "jit(train_step)/uplink/uplink/jit(sparsign_pack2bit_op)/scatter": "uplink",
        "jit(train_step)/counters/psum": "counters",
        "jit(train_step)/shard_map/xor": "unscoped",
        "jit(server_fn)/fwd_bwd_extra/mul": "unscoped",
        "": "unscoped",
    }
    for op_name, want in cases.items():
        assert scopes.layer_of(op_name) == want, op_name


def test_step_scopes_on_cpu():
    """Compiled on the CPU with the jnp backend, the step's operations take
    their layers by the innermost rule: the backward (``transpose(jvp())``)
    and the rematerialised forward (``checkpoint``) fall to ``fwd_bwd``; the
    fused path's decode + update runs under ``server`` alone, and the
    bucketed wire's decode-sum, called inside the exchange, falls to
    ``server`` too."""
    from repro.launch.mesh import make_mesh

    layer_of = _bench_scopes().layer_of
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])

    def op_names(**step_kw):
        step, state, batch = _simple_step(mesh, "jnp", **step_kw)
        with jax.sharding.set_mesh(mesh):
            hlo = step.lower(state, batch).compile().as_text()
        return {n for _, _, n in _instructions(hlo)}

    names = op_names()
    layers = {}
    for n in names:
        layers.setdefault(layer_of(n), []).append(n)
    assert set(layers) >= {"fwd_bwd", "uplink", "exchange", "server", "counters"}
    backward = [n for n in names if "transpose(jvp(" in n]
    remat = [n for n in names if "/checkpoint/" in n]
    assert backward and remat
    assert {layer_of(n) for n in backward + remat} == {"fwd_bwd"}
    assert not [n for n in names if "/exchange/server/" in n]
    server = [n for n in names if "/server/" in n]
    assert server and {layer_of(n) for n in server} == {"server"}
    decode = [n for n in op_names(bucketed=True) if "/exchange/server/" in n]
    assert decode and {layer_of(n) for n in decode} == {"server"}
