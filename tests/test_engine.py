"""Backend equivalence for the compression engine.

The contract the engine sells: ``jnp``, ``interpret`` and ``pallas`` are the
same algorithm bit-for-bit (shared counter-based PRNG; the kernels regenerate
it in-register). CI pins ``jnp == interpret`` on CPU; on a real TPU the same
tests pin ``jnp == pallas``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.algorithm import (MAX_LOCAL_STEPS, CompressionConfig,
                                  local_update_message)
from repro.core.budgets import BudgetConfig
from repro.core.compressors import (SCALE_PROTOCOLS, SERVER_DECODES, SPECS,
                                    get_spec)

# odd sizes exercise the canonical-view padding; bf16 the kernel upcast path
SHAPES = [(63,), (1000,), (7, 333)]
DTYPES = ["float32", "bfloat16"]
OTHER = "interpret" if jax.default_backend() != "tpu" else "pallas"

# every compressor whose spec registers a Pallas op — the kernel-vs-jnp
# equivalence matrix IS the registry, no hand-kept list
KERNEL_BACKED = sorted(n for n, s in SPECS.items() if s.pallas_op is not None)


def _cfg(compressor="sparsign", server="majority_vote", value=1.0):
    return CompressionConfig(compressor=compressor,
                             budget=BudgetConfig(kind="fixed", value=value),
                             server=server)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("compressor", KERNEL_BACKED)
def test_compress_leaf_backend_equivalence(shape, dtype, compressor):
    """jnp == kernel for values AND the decode scale (the scale round-trip:
    scaled_sign's L1/d, qsgd_1bit's norms, terngrad's local max)."""
    g = jnp.asarray(np.random.RandomState(0).randn(*shape), dtype)
    for counter_base in (0, 12345):
        a = engine.compress_leaf(g, _cfg(compressor), 9, counter_base, backend="jnp")
        b = engine.compress_leaf(g, _cfg(compressor), 9, counter_base, backend=OTHER)
        assert a.values.dtype == jnp.int8 and b.values.dtype == jnp.int8
        assert a.values.shape == g.shape
        assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
        assert np.array_equal(np.asarray(a.scale), np.asarray(b.scale))


def test_spec_registry_is_total_and_wellformed():
    """Every registered compressor has a complete, self-consistent spec row."""
    from repro.core.compressors import WIRE_FORMATS
    for name, spec in SPECS.items():
        assert spec.name == name
        assert callable(spec.api) and callable(spec.values)
        assert spec.scale_protocol in SCALE_PROTOCOLS
        assert spec.server_decode in SERVER_DECODES
        assert spec.wire_format in WIRE_FORMATS
        assert (spec.local_scale is None) == (spec.scale_protocol == "none")
        # wire_format is the declarative negotiation key: the ternary
        # compressors ride the 2-bit packed wire or its entropy-coded golomb
        # sibling; everything else is pack8/float
        assert (spec.wire_format in ("pack2", "golomb")) == spec.is_ternary
        if spec.fused_pack_op is not None:
            assert spec.wire_format != "float" and spec.pallas_op is not None
        # ternary <-> CompressionConfig.is_ternary agrees with the table
        assert _cfg(name).is_ternary == spec.is_ternary
    assert SPECS["qsgd8"].wire_format == "pack8"
    assert SPECS["identity"].wire_format == "float"
    assert SPECS["sparsign_golomb"].wire_format == "golomb"
    with pytest.raises(KeyError, match="unknown compressor"):
        get_spec("bogus")


def test_wire_mode_negotiation():
    """(compressor, server, vote_impl) -> wire mode is a pure spec lookup."""
    assert engine.wire_mode(_cfg("sparsign")) == "votes"
    assert engine.wire_mode(_cfg("noisy_sign", server="scaled_sign_ef")) == "votes"
    # shared-scale ternary + mean server: integer votes + ONE scalar
    assert engine.wire_mode(_cfg("terngrad", server="mean")) == "scaled_votes"
    assert engine.wire_mode(_cfg("sign", server="mean")) == "scaled_votes"
    # per-worker scales on ternary wires stay on the float wire
    assert engine.wire_mode(_cfg("qsgd_1bit_l2", server="mean")) == "decoded"
    assert engine.wire_mode(_cfg("scaled_sign", server="mean")) == "decoded"
    assert engine.wire_mode(_cfg("identity", server="mean")) == "decoded"
    # pack8 payloads take the 8-bit gather when the gather wire is selected,
    # decoded psum otherwise (levels cannot be reduced on the fabric)
    for server in ("mean", "majority_vote"):
        assert engine.wire_mode(_cfg("qsgd8", server=server)) == "decoded"
        assert engine.wire_mode(_cfg("qsgd8", server=server),
                                vote_impl="allgather_packed") == "pack8"
        assert engine.wire_mode(_cfg("qsgd8", server=server),
                                vote_impl="hier") == "decoded"
    # the gather impl does not perturb the ternary/float rows
    assert engine.wire_mode(_cfg("sparsign"),
                            vote_impl="allgather_packed") == "votes"
    assert engine.wire_mode(_cfg("identity", server="mean"),
                            vote_impl="allgather_packed") == "decoded"


def test_compress_leaf_shared_linf_mapped_context_is_loud():
    """Regression (PR 5): inside a mapped (multi-worker) context a shared_max
    compressor without shared_linf= must raise, not silently degrade to the
    per-worker local norm — that degrade IS the TernGrad drift PR 4 killed.
    Outside a mesh the single-worker degrade stays available (public API)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh

    g = jnp.asarray(np.random.RandomState(11).randn(64), jnp.float32)
    # outside any mapped context: degrades to the local L-inf, loudly documented
    msg = engine.compress_leaf(g, _cfg("terngrad"), 3, backend="jnp")
    assert float(msg.scale) == float(jnp.max(jnp.abs(g)))

    mesh = make_host_mesh(1, 1)

    def body(x):
        return engine.compress_leaf(x, _cfg("terngrad"), 3, backend="jnp").values

    mapped = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                           axis_names={"data"}, check_vma=False)
    with pytest.raises(ValueError, match="shared_linf"):
        with jax.sharding.set_mesh(mesh):
            jax.jit(mapped)(g)

    # supplying shared_linf inside the same mapped context is fine
    def body_ok(x):
        from repro.dist import collectives
        shared = collectives.worker_shared_linf(x, ("data",))
        return engine.compress_leaf(x, _cfg("terngrad"), 3, backend="jnp",
                                    shared_linf=shared).values

    mapped_ok = jax.shard_map(body_ok, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), axis_names={"data"},
                              check_vma=False)
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(mapped_ok)(g)
    assert out.shape == g.shape


def test_needs_shared_linf():
    assert engine.needs_shared_linf(_cfg("terngrad", server="mean"))
    assert engine.needs_shared_linf(_cfg("terngrad"))   # any server: Q needs s_t
    assert not engine.needs_shared_linf(_cfg("sparsign"))
    linf_budget = CompressionConfig(budget=BudgetConfig(kind="linf_share"))
    assert engine.needs_shared_linf(linf_budget)


def test_terngrad_shared_linf_scale_roundtrip():
    """shared_linf drives both the Bernoulli probabilities and the decode
    scale, identically on both backends (the Appendix B protocol)."""
    g = jnp.asarray(np.random.RandomState(3).randn(513), jnp.float32)
    shared = jnp.float32(2.5 * float(jnp.max(jnp.abs(g))))
    msgs = {}
    for backend in ("jnp", OTHER):
        local = engine.compress_leaf(g, _cfg("terngrad"), 5, backend=backend)
        m = engine.compress_leaf(g, _cfg("terngrad"), 5, backend=backend,
                                 shared_linf=shared)
        assert float(m.scale) == float(shared)
        assert float(local.scale) == float(jnp.max(jnp.abs(g)))
        # a larger normalizer keeps fewer coordinates on average
        assert float(jnp.sum(jnp.abs(m.values))) <= float(jnp.sum(jnp.abs(local.values)))
        msgs[backend] = m
    a, b = msgs.values()
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values))


def test_broadcast_quorum():
    tree = {"embed": jnp.zeros(4), "blocks": {"w": jnp.zeros(2), "b": jnp.zeros(2)}}
    # scalar broadcast
    q = engine.broadcast_quorum(3, tree)
    assert jax.tree_util.tree_leaves(q) == [3, 3, 3]
    # prefix tree: one int per top-level key fans out over the subtree
    q = engine.broadcast_quorum({"embed": 7, "blocks": 1}, tree)
    assert q["embed"] == 7 and q["blocks"] == {"w": 1, "b": 1}
    # full tree also accepted
    q = engine.broadcast_quorum({"embed": 2, "blocks": {"w": 4, "b": 5}}, tree)
    assert q["blocks"]["w"] == 4 and q["blocks"]["b"] == 5
    # validation: bad prefix / non-int / < 1 fail loudly at build time
    with pytest.raises(ValueError, match="prefix"):
        engine.broadcast_quorum({"embed": 1}, tree)
    with pytest.raises(ValueError, match="ints >= 1"):
        engine.broadcast_quorum({"embed": 0, "blocks": 1}, tree)
    with pytest.raises(ValueError, match="ints >= 1"):
        engine.broadcast_quorum({"embed": 1.5, "blocks": 1}, tree)
    with pytest.raises(ValueError, match="ints >= 1"):
        engine.broadcast_quorum(0, tree)


@pytest.mark.parametrize("server", ["majority_vote", "scaled_sign_ef", "mean"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_server_apply_backend_equivalence(server, dtype):
    rng = np.random.RandomState(1)
    p = jnp.asarray(rng.randn(777), dtype)
    vote_sum = jnp.asarray(rng.randint(-5, 6, 777), jnp.int32)
    ef = jnp.asarray(rng.randn(777), jnp.float32)
    kw = dict(lr=0.05, ef=ef, n_sel=jnp.float32(4.0))
    a_p, a_ef = engine.server_apply(p, vote_sum, _cfg(server=server), backend="jnp", **kw)
    b_p, b_ef = engine.server_apply(p, vote_sum, _cfg(server=server), backend=OTHER, **kw)
    assert a_p.dtype == p.dtype and b_p.dtype == p.dtype
    assert np.array_equal(np.asarray(a_p), np.asarray(b_p))
    assert np.array_equal(np.asarray(a_ef), np.asarray(b_ef))


@pytest.mark.parametrize("backend", ["jnp", OTHER])
def test_server_apply_sharded_scale_matches_unsharded(backend):
    """streamed-mode contract: per-shard server_apply with an l1_reduce over the
    shards == one whole-leaf server_apply, for the EF server. The non-jnp case
    exercises ef_server_op's external-scale parameter on partial shards."""
    rng = np.random.RandomState(2)
    n, k = 1024, 4
    p = jnp.asarray(rng.randn(n), jnp.float32)
    votes = jnp.asarray(rng.randint(-3, 4, n), jnp.int32)
    ef = jnp.asarray(rng.randn(n), jnp.float32)
    cfg = _cfg(server="scaled_sign_ef")
    whole_p, whole_ef = engine.server_apply(p, votes, cfg, lr=0.1, ef=ef,
                                            n_sel=2.0, backend="jnp")
    # the cross-shard-reduced L1 the streamed trainer would psum (computed here
    # with the same whole-leaf reduction so the comparison is bitwise)
    total_l1 = jnp.sum(jnp.abs(votes.astype(jnp.float32) / 2.0 + ef))
    got_p, got_ef = [], []
    for j in range(k):
        sl = slice(j * (n // k), (j + 1) * (n // k))
        sp, se = engine.server_apply(
            p[sl], votes[sl], cfg, lr=0.1, ef=ef[sl], n_sel=2.0,
            leaf_size=n, l1_reduce=lambda part: total_l1, backend=backend)
        got_p.append(sp)
        got_ef.append(se)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(got_p)), np.asarray(whole_p))
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(got_ef)), np.asarray(whole_ef))


def test_server_apply_mean_scale():
    """The scaled_votes decode: mean rule with a shared scale == decoding the
    votes by hand. scale=None stays bitwise-identical to the legacy path."""
    rng = np.random.RandomState(8)
    p = jnp.asarray(rng.randn(257), jnp.float32)
    votes = jnp.asarray(rng.randint(-3, 4, 257), jnp.int32)
    scale = jnp.float32(0.37)
    got, _ = engine.server_apply(p, votes, _cfg("terngrad", server="mean"),
                                 lr=0.1, n_sel=4.0, scale=scale, backend="jnp")
    want = p - jnp.float32(0.1) * (votes.astype(jnp.float32) / 4.0 * scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    plain, _ = engine.server_apply(p, votes, _cfg(server="mean"), lr=0.1,
                                   n_sel=4.0, backend="jnp")
    one, _ = engine.server_apply(p, votes, _cfg(server="mean"), lr=0.1,
                                 n_sel=4.0, scale=jnp.float32(1.0), backend="jnp")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(one))


def test_backend_resolution(monkeypatch):
    monkeypatch.delenv(engine.ENV_VAR, raising=False)
    auto = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert engine.resolve_backend() == auto
    assert engine.resolve_backend("auto") == auto
    monkeypatch.setenv(engine.ENV_VAR, "interpret")
    assert engine.resolve_backend() == "interpret"
    assert engine.resolve_backend("jnp") == "jnp"  # explicit beats env
    monkeypatch.setenv(engine.ENV_VAR, "nope")
    with pytest.raises(ValueError):
        engine.resolve_backend()


def test_env_var_drives_dispatch(monkeypatch):
    """The env-var path end-to-end: backend=None + $REPRO_KERNEL_BACKEND must
    actually steer dispatch (kernel vs reference) and stay bitwise-equal."""
    g = jnp.asarray(np.random.RandomState(5).randn(513), jnp.float32)
    monkeypatch.setenv(engine.ENV_VAR, "jnp")
    a = engine.compress_leaf(g, _cfg(), 3, 7)
    monkeypatch.setenv(engine.ENV_VAR, OTHER)
    b = engine.compress_leaf(g, _cfg(), 3, 7)
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
    p = jnp.asarray(np.random.RandomState(6).randn(513), jnp.float32)
    v = jnp.asarray(np.random.RandomState(7).randint(-3, 4, 513), jnp.int8)
    pb, _ = engine.server_apply(p, v, _cfg(), lr=0.1)
    monkeypatch.setenv(engine.ENV_VAR, "jnp")
    pa, _ = engine.server_apply(p, v, _cfg(), lr=0.1)
    assert np.array_equal(np.asarray(pa), np.asarray(pb))


def test_vote_server_predicates():
    assert engine.is_vote_server(_cfg(server="majority_vote"))
    assert engine.is_vote_server(_cfg(server="scaled_sign_ef"))
    assert not engine.is_vote_server(_cfg(server="mean"))
    assert engine.needs_server_ef("scaled_sign_ef")
    assert not engine.needs_server_ef("majority_vote")


def test_unknown_server_raises():
    with pytest.raises(ValueError, match="server rule"):
        engine.server_apply(jnp.zeros(8), jnp.zeros(8, jnp.int32),
                            _cfg(server="bogus"), lr=0.1)


def test_local_step_config_budget_fallback():
    cfg = _cfg(value=3.0)
    assert engine.local_budget_value(cfg) == 3.0            # fixed B_g doubles as B_l
    cfg2 = CompressionConfig(budget=BudgetConfig(value=3.0), local_budget=10.0)
    assert engine.local_budget_value(cfg2) == 10.0
    lc = engine.local_step_config(cfg2)
    assert lc.compressor == "sparsign" and lc.budget.kind == "fixed"
    assert lc.budget.value == 10.0 and lc.local_steps == 1
    # BudgetConfig.local_value sits between the two
    cfg3 = CompressionConfig(budget=BudgetConfig(value=3.0, local_value=7.0))
    assert engine.local_budget_value(cfg3) == 7.0
    # non-fixed budget kinds don't leak their value (an nnz fraction) into B_l
    cfg4 = CompressionConfig(budget=BudgetConfig(kind="target_sparsity", value=0.01))
    assert engine.local_budget_value(cfg4) == 1.0


def test_tau_overflow_guard():
    with pytest.raises(ValueError, match="local_steps"):
        CompressionConfig(local_steps=0)
    with pytest.raises(ValueError, match="local_steps"):
        CompressionConfig(local_steps=MAX_LOCAL_STEPS + 1)


def test_local_update_accumulator_is_int32():
    """Regression for the int8 accumulator: with tau=200 and a saturating local
    budget every inner step votes +1, so the accumulated message must be
    exactly +tau per coordinate (int8 would have wrapped at 128)."""
    tau = 200
    cfg = CompressionConfig(compressor="identity", local_budget=1e9, local_steps=tau)
    w0 = jnp.ones((64,), jnp.float32)
    grad_fn = lambda w, c: jnp.ones_like(w)   # constant positive gradient
    msg = local_update_message(w0, grad_fn, cfg, eta_l=0.0, seed=3)
    assert np.all(np.asarray(msg.values) == float(tau)), np.asarray(msg.values)[:4]
