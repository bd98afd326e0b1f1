"""Elastic participation: weighted, participation-normalized voting.

Blocking tier-1 coverage (single device): the weighted vote->update kernel
bitwise against its oracle (odd shapes, bf16, and the weights == 1 legacy
identity), ParticipationSpec build-time validation, the full-participation ==
legacy bitwise pins for all four wire modes at M = 1, the masked shared-linf,
the elastic wire-billing identities, and the masked-payload-zero analysis
rule. The multi-worker chaos harness (50% per-round dropout on every gather
wire) and the M-invariance pin run in tests/mdev/check_fault_tolerance.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.dist import collectives
from repro.dist.collectives import ParticipationSpec
from repro.kernels.vote_update.ops import vote_update_op, weighted_vote_update_op
from repro.kernels.vote_update.ref import vote_update_ref, weighted_vote_update_ref

SHAPES = [(63,), (1000,), (7, 333), (513, 511)]
DTYPES = ["float32", "bfloat16"]


def _weighted_votes(shape, m=5, seed=0, uniform=False):
    """(wvotes, wtot) for m workers of random ternary votes and weights."""
    rng = np.random.RandomState(seed)
    votes = rng.randint(-1, 2, (m,) + shape).astype(np.float32)
    w = np.ones(m, np.float32) if uniform else rng.uniform(0.5, 2.0, m).astype(np.float32)
    wv = jnp.asarray(np.tensordot(w, votes, axes=(0, 0)), jnp.float32)
    return wv, jnp.float32(w.sum())


# ---------------------------------------------------------------------------
# weighted vote->update kernel == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_weighted_vote_update_matches_ref(shape, dtype):
    w = jnp.asarray(np.random.RandomState(1).randn(*shape), dtype)
    wv, wtot = _weighted_votes(shape)
    for q_frac in (0.25, 0.5, 1.0):
        got = weighted_vote_update_op(w, wv, wtot, 0.05, q_frac=q_frac)
        want = weighted_vote_update_ref(w, wv, wtot, 0.05, q_frac)
        assert got.dtype == w.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want)), (shape, dtype, q_frac)


def test_weighted_vote_update_per_coordinate_wtot():
    """wtot may vary per coordinate (per-leaf quorum trees under elastic
    participation); the kernel must apply the deadband pointwise."""
    shape = (33, 65)
    w = jnp.asarray(np.random.RandomState(2).randn(*shape), jnp.float32)
    wv, _ = _weighted_votes(shape, seed=3)
    wtot = jnp.asarray(np.random.RandomState(4).uniform(1.0, 5.0, shape), jnp.float32)
    got = weighted_vote_update_op(w, wv, wtot, 0.1, q_frac=0.5)
    want = weighted_vote_update_ref(w, wv, wtot, 0.1, 0.5)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("quorum", [1, 2, 3, 4])
def test_weighted_vote_update_weights_one_is_legacy(quorum):
    """Uniform weights + full participation recover the integer-quorum kernel
    BITWISE: f32 sums of ternary votes are exact integers and q_frac * M
    reproduces the integer threshold exactly on a power-of-two fleet."""
    m, shape = 4, (129,)
    w = jnp.asarray(np.random.RandomState(5).randn(*shape), jnp.float32)
    votes = np.random.RandomState(6).randint(-m, m + 1, shape)
    legacy = vote_update_op(w, jnp.asarray(votes, jnp.int32), 0.05, quorum=quorum)
    elastic = weighted_vote_update_op(w, jnp.asarray(votes, jnp.float32),
                                      jnp.float32(m), 0.05, q_frac=quorum / m)
    assert np.array_equal(np.asarray(legacy), np.asarray(elastic))
    assert np.array_equal(
        np.asarray(vote_update_ref(w, jnp.asarray(votes, jnp.int32), 0.05, quorum)),
        np.asarray(weighted_vote_update_ref(w, jnp.asarray(votes, jnp.float32),
                                            jnp.float32(m), 0.05, quorum / m)))


# ---------------------------------------------------------------------------
# ParticipationSpec: loud build-time validation
# ---------------------------------------------------------------------------

def test_participation_spec_validation():
    ParticipationSpec(q_frac=1.0)                       # inclusive upper edge
    ParticipationSpec(q_frac=0.25, weights=(1.0, 2.0), dropout=0.5)
    for bad_q in (0.0, -0.5, 1.5, 2):
        with pytest.raises(ValueError, match="quorum fraction"):
            ParticipationSpec(q_frac=bad_q)
    for bad_w in ((0.0, 1.0), (-1.0,), (float("inf"), 1.0), ()):
        with pytest.raises(ValueError, match="weights"):
            ParticipationSpec(weights=bad_w)
    for bad_d in (1.0, -0.1):
        with pytest.raises(ValueError, match="dropout"):
            ParticipationSpec(dropout=bad_d)


def test_participation_spec_resolve_and_weights():
    spec = ParticipationSpec()
    assert spec.is_uniform
    assert spec.resolve_q_frac(2, 8) == 0.25            # legacy quorum / M
    assert ParticipationSpec(q_frac=0.75).resolve_q_frac(2, 8) == 0.75
    for bad_quorum in (0, 9):
        with pytest.raises(ValueError, match="quorum fraction"):
            spec.resolve_q_frac(bad_quorum, 8)
    w = ParticipationSpec(weights=(1.5, 0.5)).weights_array(2)
    assert np.array_equal(np.asarray(w), [1.5, 0.5])
    with pytest.raises(ValueError, match="workers"):
        ParticipationSpec(weights=(1.0, 1.0)).weights_array(3)
    assert np.array_equal(np.asarray(spec.weights_array(3)), [1.0, 1.0, 1.0])


def test_participation_rejects_ef_server_at_build():
    """scaled_sign_ef keeps a full-fleet-calibrated residual; normalizing it
    to a shifting reporting set would corrupt it — must fail at step build."""
    with pytest.raises(ValueError, match="scaled_sign_ef"):
        engine.check_participation_server("scaled_sign_ef", "sparsign")
    engine.check_participation_server("majority_vote", "sparsign")
    engine.check_participation_server("mean", "qsgd8")


def test_make_vote_wire_participation_type_is_loud():
    with pytest.raises(TypeError, match="ParticipationSpec"):
        collectives.make_vote_wire("psum", ("data",), participation={"q_frac": 0.5})


# ---------------------------------------------------------------------------
# masked shared-linf: non-reporting workers are excluded from the max
# ---------------------------------------------------------------------------

def test_worker_shared_linf_mask_excludes_nonreporting():
    gs = jnp.asarray([[1.0, -2.0], [10.0, 3.0], [-4.0, 0.5]])
    mask = jnp.asarray([True, False, True])             # drop the |10| holder
    full = jax.vmap(lambda g: collectives.worker_shared_linf(g, ("w",)),
                    axis_name="w")(gs)
    masked = jax.vmap(lambda g, m: collectives.worker_shared_linf(g, ("w",), mask=m),
                      axis_name="w")(gs, mask)
    assert np.all(np.asarray(full) == 10.0)
    assert np.all(np.asarray(masked) == 4.0)
    none = jax.vmap(lambda g, m: collectives.worker_shared_linf(g, ("w",), mask=m),
                    axis_name="w")(gs, jnp.zeros(3, bool))
    assert np.all(np.asarray(none) == 0.0)              # empty round: no scale


# ---------------------------------------------------------------------------
# elastic wire billing identities
# ---------------------------------------------------------------------------

def test_elastic_wire_billing_identities():
    from repro.analysis import drivers
    m, n = 8, 4096
    # psum family: the participation count rides as a second full-width f32 psum
    elastic = drivers.mode_wire("votes", m, elastic=True)
    assert elastic.wire_bytes(n) == 2.0 * collectives.decoded_wire_bytes(n, m)
    assert drivers.mode_wire("votes", m).wire_bytes(n) < elastic.wire_bytes(n)
    # ternary gather: one (1,) f32 weight per peer rides the gather as a scalar
    gl, ge = (drivers.mode_wire("golomb", m), drivers.mode_wire("golomb", m, elastic=True))
    assert gl.weight_bytes() == 0.0 and ge.weight_bytes() == (m - 1) * 4.0
    # pack8: the per-leaf side channel widens from (scale,) to (scale*w, w)
    p8l, p8e = (drivers.mode_wire("pack8", m), drivers.mode_wire("pack8", m, elastic=True))
    assert p8l.scalar_bytes() == (m - 1) * 4.0
    assert p8e.scalar_bytes() == (m - 1) * 8.0


# ---------------------------------------------------------------------------
# full participation == legacy, all four wire modes, M = 1
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.models.model import Model
    cfg = ModelConfig(name="part-tiny", family="dense", n_layers=2, d_model=32,
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


def _one_step(model, params, batch, mesh, comp, **cfg_kw):
    from repro.launch.mesh import make_mesh
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step
    scfg = TrainStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                           worker_axes=("data",), donate=False, **cfg_kw)
    step = build_train_step(model, scfg, mesh)
    state = init_state(params, server=comp.server, seed=7)
    with jax.sharding.set_mesh(mesh):
        out, metrics = step(state, batch)
    return jax.tree_util.tree_map(np.asarray, out.params), metrics


@pytest.mark.parametrize("mode,compressor,server,vote_impl", [
    ("votes", "sparsign", "majority_vote", "psum"),
    ("votes", "sparsign", "majority_vote", "allgather_packed"),
    ("scaled_votes", "terngrad", "mean", "psum"),
    ("pack8", "qsgd8", "mean", "allgather_packed"),
    ("decoded", "qsgd8", "mean", "psum"),
])
def test_elastic_full_participation_bitwise_equals_legacy(mode, compressor,
                                                          server, vote_impl):
    """ParticipationSpec with uniform weights, zero dropout and q_frac ==
    quorum/M must be BITWISE the legacy fixed-quorum round on every wire
    mode (the tentpole's no-regression pin; the 8-worker version runs in
    tests/mdev/check_fault_tolerance.py)."""
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = _tiny_batch(model.cfg.vocab_size)
    comp = CompressionConfig(compressor=compressor,
                             budget=BudgetConfig(kind="fixed", value=1.0),
                             server=server)
    legacy, _ = _one_step(model, params, batch, mesh, comp,
                          vote_impl=vote_impl, quorum=1)
    elastic, metrics = _one_step(model, params, batch, mesh, comp,
                                 vote_impl=vote_impl, quorum=1,
                                 participation=ParticipationSpec(q_frac=1.0))
    moved = any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(legacy), jax.tree_util.tree_leaves(params)))
    assert moved, "the step must actually update params"
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(legacy)[0],
            jax.tree_util.tree_flatten_with_path(elastic)[0]):
        assert np.array_equal(a, b), (mode, jax.tree_util.keystr(ka))
    assert float(metrics["participated"]) == 1.0


# ---------------------------------------------------------------------------
# masked-payload-zero: the analysis rule actually blocks
# ---------------------------------------------------------------------------

def _gather_fn(masked: bool):
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("data",))

    def inner(x, m):
        msg = x.astype(jnp.int8)
        if masked:
            msg = jnp.where(m, msg, jnp.zeros_like(msg))
        return jax.lax.all_gather(msg, "data")

    def fn(x, m):
        return jax.shard_map(inner, mesh=mesh, in_specs=(P("data"), P()),
                             out_specs=P(None), check_vma=False)(x, m)

    return mesh, fn


def test_masked_payload_zero_rule_blocks_unmasked_gather():
    """An integer payload gathered without a participation gate (select_n in
    its producer chain) must produce exactly one blocking finding; the
    jnp.where-masked twin must pass clean."""
    from repro.analysis.jaxpr_audit import MaskedPayloadZero
    from repro.launch.mesh import make_mesh
    x = jnp.ones((8, 128), jnp.float32)
    m = jnp.bool_(True)
    rule = MaskedPayloadZero()
    mesh, bad = _gather_fn(masked=False)
    with jax.sharding.set_mesh(mesh):
        findings = rule.check("unmasked", bad, x, m)
    assert len(findings) == 1 and "no participation mask" in findings[0].message
    mesh, good = _gather_fn(masked=True)
    with jax.sharding.set_mesh(mesh):
        assert rule.check("masked", good, x, m) == []
