"""The uplink wire layer: fused sparsign->2-bit kernel, VoteWire abstraction,
wire-native engine messages, and the quorum deadband.

Blocking tier-1 coverage (single device); the multi-worker bitwise wire
equivalence (all three wires x both train modes) runs in tests/mdev/check_wires.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.dist import collectives
from repro.kernels import common
from repro.kernels.pack2bit.ops import pack2bit_op, unpack2bit_sum_op
from repro.kernels.pack2bit.ref import pack2bit_ref, unpack2bit_sum_ref
from repro.kernels.sparsign.ops import sparsign_op
from repro.kernels.sparsign_pack2bit.ops import sparsign_pack2bit_op
from repro.kernels.sparsign_pack2bit.ref import sparsign_pack2bit_ref

SHAPES = [(63,), (1000,), (7, 333), (513, 511)]
DTYPES = ["float32", "bfloat16"]


# ---------------------------------------------------------------------------
# fused kernel == two-pass chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_uplink_matches_two_pass(shape, dtype):
    g = jnp.asarray(np.random.RandomState(0).randn(*shape), dtype)
    for budget, seed, base in [(0.3, 1, 0), (1.5, 99, 12345), (50.0, 7, 2**20)]:
        fused = sparsign_pack2bit_op(g, budget, seed, base)
        two_pass = pack2bit_op(sparsign_op(g, budget, seed, base))
        ref = sparsign_pack2bit_ref(g, budget, seed, base)
        assert fused.dtype == jnp.uint8
        assert np.array_equal(np.asarray(fused), np.asarray(two_pass)), (shape, dtype, budget)
        assert np.array_equal(np.asarray(fused), np.asarray(ref)), (shape, dtype, budget)


def test_fused_uplink_no_int8_hbm_intermediate():
    """The whole point of the fusion: gradient -> wire bytes with no int8
    ternary tensor at the HBM level; the two-pass chain necessarily has one.
    The pin is the declarative per-spec rule (spec.hbm_limits), not a
    hand-written count."""
    from repro.analysis.jaxpr_audit import check_fused_uplink
    from repro.core.compressors import get_spec
    g = jnp.asarray(np.random.RandomState(1).randn(4096), jnp.float32)
    assert check_fused_uplink(get_spec("sparsign"), g, param=1.0) == []
    two_pass = common.int8_hbm_elems(lambda x: pack2bit_op(sparsign_op(x, 1.0, 7)), g)
    assert two_pass >= g.size


# ---------------------------------------------------------------------------
# fused decode-sum (the allgather_packed downlink side)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 3, 8])
@pytest.mark.parametrize("n", [63, 1000])
def test_unpack_sum_fused_matches_ref(m, n):
    rng = np.random.RandomState(2)
    votes = [jnp.asarray(rng.randint(-1, 2, n), jnp.int8) for _ in range(m)]
    gathered = jnp.stack([pack2bit_op(v) for v in votes])
    got = unpack2bit_sum_op(gathered, n, (n,))
    want = common.from_2d(unpack2bit_sum_ref(gathered), n, (n,))
    oracle = sum(np.asarray(v, np.int32) for v in votes)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got), oracle)


def test_packed_decode_sum_no_int8_hbm_intermediate():
    from repro.analysis.jaxpr_audit import NoHbmIntermediate
    gathered = jnp.stack([pack2bit_op(jnp.asarray(
        np.random.RandomState(s).randint(-1, 2, 4096), jnp.int8)) for s in range(4)])
    rule = NoHbmIntermediate(jnp.int8)
    assert rule.check("unpack2bit_sum",
                      lambda p: unpack2bit_sum_op(p, 4096, (4096,)),
                      gathered) == []
    unfused = common.int8_hbm_elems(
        lambda p: common.from_2d(unpack2bit_sum_ref(p), 4096, (4096,)), gathered)
    assert unfused >= 4 * 4096


def test_fused_server_no_vote_sum_in_hbm():
    """The fused decode + update writes no int32 vote sum and no int8 copy of
    it; the chain it replaces writes both, each as large as the leaf."""
    from repro.kernels.vote_update.ops import vote_update_op
    n, shape = 4096, (64, 64)
    gathered = jnp.stack([pack2bit_op(jnp.asarray(
        np.random.RandomState(s).randint(-1, 2, shape), jnp.int8)) for s in range(4)])
    w = jnp.asarray(np.random.RandomState(9).randn(*shape), jnp.bfloat16)
    comp = CompressionConfig(compressor="sparsign", server="majority_vote")

    def fused(g, p):
        return engine.server_apply_packed(p, g, comp, lr=0.01, backend="interpret")

    def chain(g, p):
        votes = unpack2bit_sum_op(g, n, shape).astype(jnp.int8)
        return vote_update_op(p, votes, 0.01)

    for count in (common.int32_hbm_elems, common.int8_hbm_elems):
        assert count(fused, gathered, w) < n, count   # scalars only
        assert count(chain, gathered, w) >= n, count
    assert np.array_equal(np.asarray(jax.jit(fused)(gathered, w)),
                          np.asarray(jax.jit(chain)(gathered, w)))


# ---------------------------------------------------------------------------
# VoteWire construction + ledger
# ---------------------------------------------------------------------------

def test_make_vote_wire_validation():
    mesh = None  # sizes unused on the error paths
    with pytest.raises(ValueError, match="unknown vote_impl"):
        collectives.make_vote_wire("bogus", ("data",), mesh)
    # hier with a flat worker domain must fail LOUDLY at build time, not
    # silently substitute the flat psum wire
    with pytest.raises(ValueError, match="exactly two worker axes"):
        collectives.make_vote_wire("hier", ("data",), mesh)
    with pytest.raises(ValueError, match="exactly two worker axes"):
        collectives.make_vote_wire("hier", ("pod", "data", "extra"), mesh)


def test_vote_wire_formats_and_ledger():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    psum = collectives.make_vote_wire("psum", ("data",), mesh)
    packed = collectives.make_vote_wire("allgather_packed", ("data",), mesh)
    assert not psum.wants_packed and packed.wants_packed
    assert psum.n_workers == 1 and packed.n_workers == 1

    # ledger first principles at M=16 (psum wire: int8 sums fit M<=127)
    p16 = collectives.VoteWire(axes=("data",), n_workers=16)
    g16 = collectives.PackedVoteWire(axes=("data",), n_workers=16)
    n = 1 << 20
    assert p16.wire_bytes(n) == pytest.approx(2 * 15 / 16 * n)
    # all-gather wire: (M-1) x real padded payload — the padding is part of
    # the wire format, so the ledger must count it
    assert g16.wire_bytes(n) == 15 * collectives.packed_nbytes(n)
    assert collectives.packed_nbytes(1) == common.SUBLANE_PAD * (common.LANES // 4)
    assert collectives.packed_nbytes(n) == n // 4   # aligned case: exactly 2 bit/coord

    # hier ledger = narrow inner ring + widened outer ring
    h = collectives.HierVoteWire(axes=("pod", "data"), n_workers=32,
                                 inner_size=16, outer_size=2)
    assert h.wire_bytes(n) == pytest.approx(2 * 15 / 16 * n + 2 * 1 / 2 * n)


def test_packed_wire_nnz_and_mask():
    wire = collectives.PackedVoteWire(axes=("data",), n_workers=4)
    t = jnp.asarray(np.random.RandomState(3).randint(-1, 2, 1000), jnp.int8)
    packed = pack2bit_op(t)
    # nnz off the packed bytes == nnz of the ternary tensor
    assert float(wire.message_nnz(packed)) == float(jnp.sum(jnp.abs(t)))
    # masking a packed message zeroes every vote (packed 0 decodes to 0)
    masked = wire.mask_message(packed, jnp.bool_(False))
    assert float(wire.message_nnz(masked)) == 0.0
    assert np.array_equal(np.asarray(wire.mask_message(packed, jnp.bool_(True))),
                          np.asarray(packed))


# ---------------------------------------------------------------------------
# engine wire-native messages
# ---------------------------------------------------------------------------

def _cfg(compressor="sparsign", value=2.0):
    return CompressionConfig(compressor=compressor,
                             budget=BudgetConfig(kind="fixed", value=value),
                             server="majority_vote")


OTHER = "interpret" if jax.default_backend() != "tpu" else "pallas"


@pytest.mark.parametrize("backend", ["jnp", OTHER])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("compressor", ["sparsign", "noisy_sign", "terngrad"])
def test_compress_leaf_wire_native(backend, dtype, compressor):
    """compress_leaf(wire=packed) returns the same wire bytes as packing the
    int8 message, on every backend (fused kernel vs two-pass reference), for
    every fused-kernel compressor — and the decode scale rides alongside."""
    wire = collectives.PackedVoteWire(axes=("data",), n_workers=4)
    g = jnp.asarray(np.random.RandomState(4).randn(7, 333), dtype)
    msg_int8 = engine.compress_leaf(g, _cfg(compressor), 9, 123, backend=backend)
    msg_packed = engine.compress_leaf(g, _cfg(compressor), 9, 123, backend=backend, wire=wire)
    assert msg_int8.values.dtype == jnp.int8
    assert msg_packed.values.dtype == jnp.uint8
    view, _ = common.to_2d(msg_int8.values.reshape(-1))
    assert np.array_equal(np.asarray(msg_packed.values), np.asarray(pack2bit_ref(view)))
    assert np.array_equal(np.asarray(msg_packed.scale), np.asarray(msg_int8.scale))


@pytest.mark.parametrize("compressor,param", [("noisy_sign", 0.3), ("terngrad", None)])
def test_new_fused_uplinks_no_int8_hbm_intermediate(compressor, param):
    """Acceptance pin: noisy_sign and terngrad reach the packed wire through a
    single-pass kernel — no int8 ternary tensor at the HBM level (the two-pass
    chain necessarily has one)."""
    from repro.analysis.jaxpr_audit import check_fused_uplink
    from repro.core.compressors import get_spec
    g = jnp.asarray(np.random.RandomState(6).randn(4096), jnp.float32)
    spec = get_spec(compressor)
    p = param if param is not None else float(jnp.max(jnp.abs(g)))
    assert check_fused_uplink(spec, g, param=p) == [], compressor
    two_pass = common.int8_hbm_elems(
        lambda x: pack2bit_op(spec.pallas_op(x, p, 7, interpret=True),
                              interpret=True), g)
    assert two_pass >= g.size
    # and the fused bytes == pack2bit(reference compressor) byte-for-byte
    want_view, _ = common.to_2d(spec.values(g, p, 7, 0).reshape(-1))
    got = spec.fused_pack_op(g, p, 7, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(pack2bit_ref(want_view)))


@pytest.mark.parametrize("backend", ["jnp", OTHER])
def test_compress_leaf_wire_two_pass_fallback(backend):
    """Ternary compressors without a fused kernel still speak the packed wire."""
    wire = collectives.PackedVoteWire(axes=("data",), n_workers=4)
    g = jnp.asarray(np.random.RandomState(5).randn(513), jnp.float32)
    cfg = _cfg(compressor="sign")
    msg = engine.compress_leaf(g, cfg, 1, backend=backend, wire=wire)
    view, _ = common.to_2d(jnp.sign(g).astype(jnp.int8))
    assert np.array_equal(np.asarray(msg.values), np.asarray(pack2bit_ref(view)))


def test_compress_leaf_wire_rejects_non_ternary():
    wire = collectives.PackedVoteWire(axes=("data",), n_workers=4)
    g = jnp.zeros((8,), jnp.float32)
    with pytest.raises(ValueError, match="ternary"):
        engine.compress_leaf(g, _cfg(compressor="identity"), 0, wire=wire)


# ---------------------------------------------------------------------------
# end-to-end on a 1-device mesh: wires agree bitwise; quorum deadband
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.models.model import Model
    cfg = ModelConfig(name="wire-tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      pattern=(LayerSpec(mixer="attn"),), dtype="float32",
                      attn_chunk=8, q_chunk=8, loss_chunk=8, remat=False)
    return Model(cfg)


def _tiny_batch(vocab, b=2, s=8, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "inputs": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }


def _one_step(model, params, batch, mesh, comp=None, **cfg_kw):
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step
    if comp is None:
        comp = CompressionConfig(compressor="sparsign",
                                 budget=BudgetConfig(kind="fixed", value=2.0),
                                 server="majority_vote")
    scfg = TrainStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                           worker_axes=("data",), donate=False, **cfg_kw)
    step = build_train_step(model, scfg, mesh)
    state = init_state(params, server=comp.server, seed=7)
    with jax.sharding.set_mesh(mesh):
        out, metrics = step(state, batch)
    return jax.tree_util.tree_map(np.asarray, out.params), metrics


def test_simple_step_wires_bitwise_equal_single_device():
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)

    ref, m_ref = _one_step(model, params, batch, mesh, vote_impl="psum")
    for vote_impl in ("allgather_packed",):
        for backend in ("jnp", OTHER):
            got, m = _one_step(model, params, batch, mesh,
                               vote_impl=vote_impl, backend=backend)
            for (ka, a), (kb, b) in zip(
                    jax.tree_util.tree_flatten_with_path(ref)[0],
                    jax.tree_util.tree_flatten_with_path(got)[0]):
                assert np.array_equal(a, b), (vote_impl, backend, jax.tree_util.keystr(ka))
    # the ledger metric is emitted and matches the wire's own accounting
    # (M=1: both ring collectives move zero bytes)
    assert float(m["wire_bytes_per_device"]) == 0.0
    assert float(m_ref["wire_bytes_per_device"]) == 0.0


@pytest.mark.parametrize("backend", ["jnp", OTHER])
def test_fused_server_step_matches_ring_bitwise(backend):
    """The benchmark's method (sparsign, majority vote, monolithic 2-bit
    gather) updates every coordinate in the fused decode + update kernel;
    the ring keeps the decode-sum and the vote update apart. One step of
    each gives bitwise the same parameters."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(kind="fixed", value=1.0),
                             server="majority_vote")
    fused, m_fused = _one_step(model, params, batch, mesh, comp=comp,
                               vote_impl="allgather_packed", backend=backend)
    ring, m_ring = _one_step(model, params, batch, mesh, comp=comp,
                             vote_impl="allgather_packed", backend=backend,
                             ring_chunk_rows=32)
    assert float(m_fused["server_fused_share"]) == 1.0
    assert float(m_ring["server_fused_share"]) == 0.0
    moved = any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(fused), jax.tree_util.tree_leaves(params)))
    assert moved, "the step must actually update params"
    for (ka, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(ring)[0],
                               jax.tree_util.tree_flatten_with_path(fused)[0]):
        assert np.array_equal(a, b), (backend, jax.tree_util.keystr(ka))


@pytest.mark.parametrize("kw", [
    {"bucketed": True},
    {"participation": collectives.ParticipationSpec(q_frac=1.0)},
    {"vote_impl": "psum"},
    {"comp": CompressionConfig(compressor="sparsign", server="scaled_sign_ef")},
], ids=["bucketed", "elastic", "psum", "scaled_sign_ef"])
def test_server_fused_share_off_the_monolithic_gather(kw):
    """Wires that need the decoded vote sum, and servers other than the
    majority vote, keep the decode-sum + update chain: the share reads 0."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    kw = {"vote_impl": "allgather_packed", "backend": "jnp", **kw}
    _, m = _one_step(model, params, _tiny_batch(model.cfg.vocab_size), mesh, **kw)
    assert float(m["server_fused_share"]) == 0.0


@pytest.mark.parametrize("compressor,server", [
    ("noisy_sign", "majority_vote"),   # votes mode through a new fused kernel
    ("terngrad", "mean"),              # scaled_votes: ternary votes + shared s_t
])
def test_simple_step_nonsparsign_wires_bitwise_equal(compressor, server):
    """Non-sparsign ternary compressors ride all wires bitwise-identically —
    the spec-negotiated wire (votes / scaled_votes) must not change the round."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    comp = CompressionConfig(compressor=compressor,
                             budget=BudgetConfig(kind="fixed", value=0.5),
                             server=server)
    ref, _ = _one_step(model, params, batch, mesh, comp=comp, vote_impl="psum")
    moved = any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(params)))
    assert moved, "the step must actually update params"
    for backend in ("jnp", OTHER):
        got, _ = _one_step(model, params, batch, mesh, comp=comp,
                           vote_impl="allgather_packed", backend=backend)
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(ref)[0],
                jax.tree_util.tree_flatten_with_path(got)[0]):
            assert np.array_equal(a, b), (compressor, backend, jax.tree_util.keystr(ka))


def test_per_leaf_quorum_tree_freezes_selected_leaves():
    """quorum as a pytree prefix: an unreachable quorum on one subtree freezes
    exactly that subtree; the rest matches the scalar-quorum run bitwise."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    shapes = model.param_shapes()
    frozen_key = "embed"
    qtree = {k: (10**6 if k == frozen_key else 1) for k in shapes}
    base, _ = _one_step(model, params, batch, mesh, quorum=1)
    got, _ = _one_step(model, params, batch, mesh, quorum=qtree)
    p0 = jax.tree_util.tree_map(np.asarray, params)
    for k in shapes:
        for a, b, c in zip(jax.tree_util.tree_leaves(got[k]),
                           jax.tree_util.tree_leaves(base[k]),
                           jax.tree_util.tree_leaves(p0[k])):
            if k == frozen_key:
                assert np.array_equal(a, c), f"{k} must be frozen by its quorum"
            else:
                assert np.array_equal(a, b), f"{k} must match the scalar-quorum run"
    # malformed quorum trees fail at build time, before tracing
    from repro.train.state import LrSchedule
    from repro.train.step_simple import TrainStepConfig, build_train_step
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    with pytest.raises(ValueError, match="prefix"):
        build_train_step(model, TrainStepConfig(
            compression=comp, lr=LrSchedule(base=0.05), worker_axes=("data",),
            quorum={"embed": 2}), mesh)
    # a quorum the wire would silently ignore is a build-time error too
    mean_comp = CompressionConfig(compressor="terngrad",
                                  budget=BudgetConfig(kind="fixed", value=1.0),
                                  server="mean")
    with pytest.raises(ValueError, match="silently ignored"):
        build_train_step(model, TrainStepConfig(
            compression=mean_comp, lr=LrSchedule(base=0.05),
            worker_axes=("data",), quorum=5), mesh)


def test_quorum_deadband_blocks_minority_updates():
    """M=1 worker can never reach a quorum of 2: params must not move."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    got, _ = _one_step(model, params, batch, mesh, quorum=2)
    for (k, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(jax.tree_util.tree_map(np.asarray, params))[0],
            jax.tree_util.tree_flatten_with_path(got)[0]):
        assert np.array_equal(a, b), jax.tree_util.keystr(k)


def test_streamed_config_exposes_vote_impl_and_quorum():
    from repro.train.state import LrSchedule
    from repro.train.step_streamed import StreamedStepConfig
    cfg = StreamedStepConfig(compression=CompressionConfig(),
                             lr=LrSchedule(base=0.1),
                             vote_impl="allgather_packed", quorum=3)
    assert cfg.vote_impl == "allgather_packed" and cfg.quorum == 3


# ---------------------------------------------------------------------------
# the pack8 (8-bit QSGD) wire: fused kernel, decode-sum, Pack8Wire, engine
# ---------------------------------------------------------------------------

from repro.kernels.pack8.ops import qsgd8_op, qsgd8_pack8_op, unpack8_sum_op
from repro.kernels.pack8.ref import (QSGD8_LEVELS, qsgd8_levels_ref,
                                     qsgd8_pack8_ref, unpack8_sum_ref)


def _qsgd8_param(g):
    from repro.core.compressors import qsgd8_scale
    return qsgd8_scale(g)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pack8_fused_matches_ref(shape, dtype):
    """Fused quantize->wire kernel == quantize-then-pad reference, byte for
    byte, across odd shapes / bf16 / counter bases (the pack8 round-trip)."""
    g = jnp.asarray(np.random.RandomState(7).randn(*shape), dtype)
    param = _qsgd8_param(g)
    for seed, base in [(1, 0), (99, 12345), (7, 2**20)]:
        fused = qsgd8_pack8_op(g, param, seed, base)
        ref = qsgd8_pack8_ref(g, param, seed, base)
        assert fused.dtype == jnp.int8
        assert np.array_equal(np.asarray(fused), np.asarray(ref)), (shape, dtype, seed)
        # leaf-shaped op unpads the same payload
        leaf = qsgd8_op(g, param, seed, base)
        assert leaf.shape == g.shape
        assert np.array_equal(np.asarray(leaf),
                              np.asarray(qsgd8_levels_ref(g, param, seed, base)))
        assert int(np.abs(np.asarray(leaf).astype(np.int32)).max()) <= QSGD8_LEVELS


def test_pack8_fused_no_int32_hbm_intermediate():
    """The fused uplink's structural guarantee: gradient -> int8 wire bytes
    with no int32 level tensor at the HBM level (the legacy generic-qsgd jnp
    chain necessarily materializes one)."""
    from repro.analysis.jaxpr_audit import check_fused_uplink
    from repro.core.compressors import _qsgd_level_values, get_spec
    g = jnp.asarray(np.random.RandomState(8).randn(4096), jnp.float32)
    # the spec declares hbm_limits=(("int32", 1),): the single scatter-start
    # index of the to_2d canonical-view pad is allowed (every canonical-view
    # op carries it); the point is no O(n) level tensor.  check_fused_uplink
    # supplies a uint32 seed, as the engine does (a python-int seed would add
    # one i32->u32 scalar conversion to the jaxpr and muddy the pin)
    assert check_fused_uplink(get_spec("qsgd8"), g) == []
    param = _qsgd8_param(g)
    legacy_i32 = common.int32_hbm_elems(
        lambda x: _qsgd_level_values(x, param, jnp.uint32(7), 0), g)
    assert legacy_i32 >= g.size


@pytest.mark.parametrize("m", [1, 3, 8, 40])  # 40 exercises worker chunking
@pytest.mark.parametrize("n", [63, 1000])
def test_unpack8_sum_matches_sequential_oracle(m, n):
    """Fused dequantize-sum == eager worker-order accumulation of the decoded
    payloads — the association the decoded-psum wire uses, which is what makes
    the pack8 wire bitwise-honest against the fp32 oracle stream. m=40 splits
    into worker chunks (the VMEM bound for large M), whose grid accumulation
    must preserve the same worker-order association."""
    rng = np.random.RandomState(9)
    payloads, scales = [], []
    for i in range(m):
        gi = jnp.asarray(rng.randn(n), jnp.float32)
        pi = _qsgd8_param(gi)
        payloads.append(qsgd8_pack8_op(gi, pi, i))
        scales.append(jnp.float32(pi))
    gathered = jnp.stack(payloads)
    scales = jnp.stack(scales)
    got = jax.jit(lambda ga, s: unpack8_sum_op(ga, s, n, (n,)))(gathered, scales)
    want = common.from_2d(unpack8_sum_ref(gathered, scales), n, (n,))
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # eager sequential oracle (rounded products, worker-order adds)
    acc = np.zeros(n, np.float32)
    for i in range(m):
        dec = np.asarray(common.from_2d(gathered[i], n, (n,)), np.float32) * np.asarray(scales)[i]
        acc = (acc + dec).astype(np.float32)
    assert np.array_equal(np.asarray(got), acc)


def test_pack8_wire_nnz_mask_and_ledger():
    wire = collectives.Pack8Wire(axes=("data",), n_workers=4)
    assert wire.native_format == "pack8" and wire.wants_packed
    g = jnp.asarray(np.random.RandomState(10).randn(1000), jnp.float32)
    payload = qsgd8_pack8_op(g, _qsgd8_param(g), 3)
    # nnz counts nonzero LEVELS (not their magnitudes)
    levels = np.asarray(common.from_2d(payload, 1000, (1000,)))
    assert float(wire.message_nnz(payload)) == float((levels != 0).sum())
    masked = wire.mask_message(payload, jnp.bool_(False))
    assert float(wire.message_nnz(masked)) == 0.0
    # ledger: (M-1) x real padded int8 payload + (M-1) gathered f32 scales
    n = 1 << 20
    assert wire.wire_bytes(n) == 3 * collectives.packed8_nbytes(n)
    assert collectives.packed8_nbytes(n) == n       # aligned: exactly 1 B/coord
    assert collectives.packed8_nbytes(1) == common.SUBLANE_PAD * common.LANES
    assert wire.scalar_bytes() == 3 * 4.0
    # integer vote wires reject an in-exchange scale loudly
    with pytest.raises(ValueError, match="pack8-wire concept"):
        collectives.VoteWire(axes=("data",), n_workers=4).exchange(
            jnp.zeros(8, jnp.int8), 8, (8,), scale=jnp.float32(1.0))


def test_wire_ledger_matches_real_payload_nbytes():
    """Satellite pin: every wire impl's ledger == the bytes of the REAL
    (padded) message buffers it exchanges, from first principles — no
    idealized d/4 or d models anywhere."""
    n = 1000  # unaligned on purpose: the pad must be counted
    g = jnp.asarray(np.random.RandomState(12).randn(n), jnp.float32)
    t = jnp.sign(g).astype(jnp.int8)

    m = 16
    psum = collectives.VoteWire(axes=("data",), n_workers=m)
    # psum payload: leaf-shaped votes in the narrowest sum dtype (no padding)
    votes = t.astype(collectives._sum_dtype(m))
    assert psum.wire_bytes(n) == pytest.approx(2 * (m - 1) / m * votes.nbytes)

    hier = collectives.HierVoteWire(axes=("pod", "data"), n_workers=m,
                                    inner_size=8, outer_size=2)
    inner_payload = t.astype(collectives._sum_dtype(8)).nbytes
    outer_payload = t.astype(collectives._sum_dtype(16)).nbytes
    assert hier.wire_bytes(n) == pytest.approx(
        2 * 7 / 8 * inner_payload + 2 * 1 / 2 * outer_payload)

    packed = collectives.PackedVoteWire(axes=("data",), n_workers=m)
    payload2 = pack2bit_op(t)
    assert packed.wire_bytes(n) == (m - 1) * payload2.nbytes

    p8 = collectives.Pack8Wire(axes=("data",), n_workers=m)
    payload8 = qsgd8_pack8_op(g, _qsgd8_param(g), 0)
    assert p8.wire_bytes(n) == (m - 1) * payload8.nbytes
    assert p8.scalar_bytes() == (m - 1) * jnp.float32(0).nbytes


def test_make_vote_wire_pack8_validation():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    wire = collectives.make_vote_wire("allgather_packed", ("data",), mesh,
                                      wire_format="pack8")
    assert isinstance(wire, collectives.Pack8Wire)
    # the pack8 payload cannot ride a fabric reduction
    for impl in ("psum", "hier"):
        with pytest.raises(ValueError, match="allgather_packed"):
            collectives.make_vote_wire(impl, ("pod", "data"), mesh,
                                       wire_format="pack8")
    with pytest.raises(ValueError, match="payload format"):
        collectives.make_vote_wire("psum", ("data",), mesh, wire_format="float")


@pytest.mark.parametrize("backend", ["jnp", OTHER])
def test_compress_leaf_pack8_wire_native(backend):
    """compress_leaf(wire=Pack8Wire) returns the canonical int8 level payload
    (fused kernel or padded reference — identical bytes) with the per-worker
    decode scale riding alongside."""
    wire = collectives.Pack8Wire(axes=("data",), n_workers=4)
    g = jnp.asarray(np.random.RandomState(13).randn(7, 333), jnp.float32)
    cfg = _cfg(compressor="qsgd8")
    msg_plain = engine.compress_leaf(g, cfg, 9, 123, backend=backend)
    msg_wire = engine.compress_leaf(g, cfg, 9, 123, backend=backend, wire=wire)
    assert msg_plain.values.dtype == jnp.int8 and msg_plain.values.shape == g.shape
    assert msg_wire.values.dtype == jnp.int8
    view, _ = common.to_2d(msg_plain.values.reshape(-1))
    assert np.array_equal(np.asarray(msg_wire.values), np.asarray(view))
    assert np.array_equal(np.asarray(msg_wire.scale), np.asarray(msg_plain.scale))
    assert float(msg_wire.scale) == float(_qsgd8_param(g))


def test_compress_leaf_wire_format_mismatch_is_loud():
    g = jnp.zeros((8,), jnp.float32)
    # ternary wire refuses pack8/float specs (pre-existing contract)
    pack2 = collectives.PackedVoteWire(axes=("data",), n_workers=4)
    with pytest.raises(ValueError, match="ternary"):
        engine.compress_leaf(g, _cfg(compressor="qsgd8"), 0, wire=pack2)
    # pack8 wire refuses ternary/float specs
    p8 = collectives.Pack8Wire(axes=("data",), n_workers=4)
    with pytest.raises(ValueError, match="pack8"):
        engine.compress_leaf(g, _cfg(compressor="sparsign"), 0, wire=p8)
    with pytest.raises(ValueError, match="pack8"):
        engine.compress_leaf(g, _cfg(compressor="identity"), 0, wire=p8)


def test_server_ef_off_the_votes_wire_is_loud():
    """scaled_sign_ef keeps a residual that only updates on the integer vote
    wire; pairing it with a pack8/float compressor must fail at build time,
    not silently train plain mean while carrying a dead full-model EF tree."""
    from repro.launch.mesh import make_host_mesh
    from repro.train.state import LrSchedule
    from repro.train.step_simple import TrainStepConfig, build_train_step
    from repro.train.step_streamed import (StreamedStepConfig,
                                           build_streamed_train_step)
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    for compressor in ("qsgd8", "identity"):
        comp = CompressionConfig(compressor=compressor,
                                 budget=BudgetConfig(kind="fixed", value=1.0),
                                 server="scaled_sign_ef")
        with pytest.raises(ValueError, match="error-feedback residual"):
            build_train_step(model, TrainStepConfig(
                compression=comp, lr=LrSchedule(base=0.05),
                worker_axes=("data",)), mesh)
        with pytest.raises(ValueError, match="error-feedback residual"):
            build_streamed_train_step(model, StreamedStepConfig(
                compression=comp, lr=LrSchedule(base=0.05),
                worker_axes=("data",), fsdp_axis="data"), mesh)


def test_simple_step_qsgd8_pack8_bitwise_equals_decoded_psum():
    """The acceptance pin at M=1: qsgd8 end-to-end on the pack8 gather wire ==
    the decoded-psum stream bitwise, jnp and kernel backends; the ledger
    metric is emitted from the pack8 wire's accounting."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    comp = CompressionConfig(compressor="qsgd8",
                             budget=BudgetConfig(kind="fixed", value=1.0),
                             server="mean")
    ref, m_ref = _one_step(model, params, batch, mesh, comp=comp, vote_impl="psum")
    moved = any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(params)))
    assert moved, "the step must actually update params"
    for backend in ("jnp", OTHER):
        got, m_got = _one_step(model, params, batch, mesh, comp=comp,
                               vote_impl="allgather_packed", backend=backend)
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(ref)[0],
                jax.tree_util.tree_flatten_with_path(got)[0]):
            assert np.array_equal(a, b), (backend, jax.tree_util.keystr(ka))
        # M=1 ring collectives move zero bytes on both wires
        assert float(m_got["wire_bytes_per_device"]) == 0.0
    assert float(m_ref["wire_bytes_per_device"]) == 0.0
