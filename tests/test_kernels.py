"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import common
from repro.kernels.ef_server.ops import ef_server_op
from repro.kernels.ef_server.ref import ef_scale, ef_server_ref
from repro.kernels.pack2bit.ops import pack2bit_op, unpack2bit_op, unpack2bit_sum_op
from repro.kernels.pack2bit.ref import pack2bit_ref, unpack2bit_ref
from repro.kernels.sparsign.ops import sparsign_op
from repro.kernels.sparsign.ref import sparsign_ref
from repro.kernels.ternary.ops import ternary_compress_op, ternary_pack2bit_op
from repro.kernels.ternary.ref import ternary_compress_ref, ternary_pack2bit_ref
from repro.kernels.ternary.rules import RULES
from repro.kernels.vote_update.ops import vote_update_op, vote_update_packed2bit_op
from repro.kernels.vote_update.ref import vote_update_packed2bit_ref, vote_update_ref

SHAPES = [(64,), (1000,), (7, 333), (2, 3, 129), (513, 511), (1 << 16,)]
DTYPES = ["float32", "bfloat16"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sparsign_kernel_matches_ref(shape, dtype):
    g = jnp.asarray(np.random.RandomState(0).randn(*shape), dtype)
    for budget, seed, base in [(0.3, 1, 0), (1.5, 99, 12345), (50.0, 7, 2**20)]:
        a = sparsign_op(g, budget, seed, base)
        b = sparsign_ref(g, budget, seed, base)
        assert a.dtype == jnp.int8 and a.shape == g.shape
        assert np.array_equal(np.asarray(a), np.asarray(b)), (shape, dtype, budget)


@given(n=st.integers(1, 5000), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_sparsign_kernel_property(n, seed):
    g = jnp.asarray(np.random.RandomState(seed % 9973).randn(n), jnp.float32)
    a = sparsign_op(g, 0.8, seed)
    b = sparsign_ref(g, 0.8, seed)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# one representative param per rule: sparsign/noisy_sign take a budget/sigma,
# the stochastic family takes a magnitude normalizer s_t
RULE_PARAMS = [("sparsign", 1.5), ("sign", 0.0), ("noisy_sign", 0.3),
               ("stochastic_ternary", 1.2)]


@pytest.mark.parametrize("shape", [(63,), (1000,), (7, 333), (513, 511)])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rule,param", RULE_PARAMS)
def test_ternary_template_matches_ref(shape, dtype, rule, param):
    """The generic ternary kernel template == the prng-based oracle, bitwise,
    over odd shapes / bf16 / nonzero counter_base — same pin the dedicated
    sparsign kernel carries."""
    g = jnp.asarray(np.random.RandomState(0).randn(*shape), dtype)
    for seed, base in [(1, 0), (99, 12345), (7, 2**20)]:
        a = ternary_compress_op(g, param, seed, base, rule=rule)
        b = ternary_compress_ref(g, param, seed, base, rule=rule)
        assert a.dtype == jnp.int8 and a.shape == g.shape
        assert set(np.unique(np.asarray(a))).issubset({-1, 0, 1})
        assert np.array_equal(np.asarray(a), np.asarray(b)), (shape, dtype, rule, seed)


@pytest.mark.parametrize("shape", [(63,), (7, 333), (513, 511)])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rule,param", RULE_PARAMS)
def test_ternary_fused_pack_matches_two_pass(shape, dtype, rule, param):
    """fused compress->pack2bit == pack2bit_op(compress_op(g)) byte-for-byte.
    noisy_sign is the sharp edge: its rule signs pure noise at zero input, so
    the kernel must zero the canonical-view padding explicitly."""
    g = jnp.asarray(np.random.RandomState(1).randn(*shape), dtype)
    for seed, base in [(3, 0), (11, 4096)]:
        fused = ternary_pack2bit_op(g, param, seed, base, rule=rule)
        two_pass = pack2bit_op(ternary_compress_op(g, param, seed, base, rule=rule))
        ref = ternary_pack2bit_ref(g, param, seed, base, rule=rule)
        assert fused.dtype == jnp.uint8
        assert np.array_equal(np.asarray(fused), np.asarray(two_pass)), (shape, rule)
        assert np.array_equal(np.asarray(fused), np.asarray(ref)), (shape, rule)


def test_ternary_template_sparsign_rule_matches_dedicated_kernel():
    """The template instantiated with the sparsign rule reproduces the
    dedicated sparsign kernel bit-for-bit — one rule table, no drift."""
    g = jnp.asarray(np.random.RandomState(2).randn(1000), jnp.float32)
    a = ternary_compress_op(g, 0.8, 7, 3, rule="sparsign")
    b = sparsign_op(g, 0.8, 7, 3)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert set(RULES) >= {"sparsign", "sign", "noisy_sign", "stochastic_ternary"}


@pytest.mark.parametrize("shape", SHAPES)
def test_pack_unpack_roundtrip(shape):
    t = jnp.asarray(np.random.RandomState(1).randint(-1, 2, size=shape), jnp.int8)
    p = pack2bit_op(t)
    assert p.dtype == jnp.uint8
    u = unpack2bit_op(p, t.size, shape)
    assert np.array_equal(np.asarray(u), np.asarray(t))
    # vs ref on the canonical view
    view, _ = common.to_2d(t.reshape(-1))
    assert np.array_equal(np.asarray(p), np.asarray(pack2bit_ref(view)))
    assert np.array_equal(np.asarray(unpack2bit_ref(pack2bit_ref(view))), np.asarray(view))


def test_pack_density():
    """Wire density: exactly 2 bits per coordinate of the canonical view."""
    t = jnp.asarray(np.random.RandomState(2).randint(-1, 2, size=(100000,)), jnp.int8)
    p = pack2bit_op(t)
    view, _ = common.to_2d(t)
    assert p.size == view.size // 4


@pytest.mark.parametrize("shape", [(512,), (33, 65), (4096,)])
def test_ef_server_fused(shape):
    rng = np.random.RandomState(3)
    d = jnp.asarray(rng.randn(*shape), jnp.float32)
    e = jnp.asarray(rng.randn(*shape), jnp.float32)
    out, ne = ef_server_op(d, e)
    ro, rne = ef_server_ref(d, e, ef_scale(d, e))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ne), np.asarray(rne), rtol=1e-6, atol=1e-6)
    # EF identity: out + new_residual == delta + old_residual (exactly, Eq. 8)
    np.testing.assert_allclose(np.asarray(out + ne), np.asarray(d + e), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("quorum", [1, 3])
def test_vote_update(dtype, quorum):
    rng = np.random.RandomState(4)
    w = jnp.asarray(rng.randn(777), dtype)
    v = jnp.asarray(rng.randint(-5, 6, size=777), jnp.int32)
    a = vote_update_op(w, v, 0.05, quorum=quorum)
    b = vote_update_ref(w, v, 0.05, quorum=quorum)
    assert a.dtype == w.dtype
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_vote_update_semantics():
    w = jnp.zeros((8,), jnp.float32)
    v = jnp.asarray([3, -2, 0, 1, -1, 5, -5, 0], jnp.int32)
    out = np.asarray(vote_update_op(w, v, 1.0))
    assert np.array_equal(out, -np.sign(np.asarray(v)).astype(np.float32))


# (350, 400): 140,000 coordinates, 274 rows padded to 288, which the block
# rule splits into three blocks of 96; its last worker's payload is zeroed,
# as the wire masks a non-participating worker
@pytest.mark.parametrize("shape,masked", [((777,), False), ((350, 400), True)])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("quorum", [1, 3])
@pytest.mark.parametrize("m", [1, 4, 5])
def test_vote_update_packed2bit_matches_chain(m, quorum, dtype, shape, masked):
    """The fused decode + update is bitwise the chain it replaces: decode-sum
    to int32, narrow to int8, vote update."""
    rng = np.random.RandomState(10 * m + quorum)
    n = int(np.prod(shape))
    gathered = jnp.stack([pack2bit_op(jnp.asarray(rng.randint(-1, 2, shape), jnp.int8))
                          for _ in range(m)])
    if masked:
        assert (gathered.shape[1], common.gather_block_rows(gathered.shape)) == (288, 96)
        if m > 1:
            gathered = gathered.at[-1].set(0)
    w = jnp.asarray(rng.randn(*shape), dtype)
    got = vote_update_packed2bit_op(w, gathered, 0.05, quorum=quorum)
    chain = vote_update_op(w, unpack2bit_sum_op(gathered, n, shape).astype(jnp.int8),
                           0.05, quorum=quorum)
    assert got.dtype == w.dtype and got.shape == w.shape
    assert np.array_equal(np.asarray(got), np.asarray(chain))
    assert np.array_equal(np.asarray(got),
                          np.asarray(vote_update_packed2bit_ref(w, gathered, 0.05, quorum)))
    moved = np.asarray(got) != np.asarray(w)
    assert moved.any() == (m >= quorum) and not moved.all()


def test_vote_update_packed2bit_rejects_foreign_payload():
    w = jnp.zeros((777,), jnp.float32)
    with pytest.raises(ValueError, match="canonical view"):
        vote_update_packed2bit_op(w, jnp.zeros((2, 64, 128), jnp.uint8), 0.1)
