"""The repro.analysis subsystem: jaxpr walker descent (incl. the historical
custom_vjp blind spot), collective-census byte math (ppermute ring hops
included), the census==ledger acceptance pin over every wire mode (monolithic
AND ring-pipelined), the gather peak-HBM floor, the HLO agreement pass,
dtype-promotion drift, and the AST repo-lint (unit cases + repo-green + the
zero-entry allowlist pin).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import drivers
from repro.analysis.framework import Finding, Report, merge, report
from repro.analysis.hlo_audit import HloJaxprAgreement
from repro.analysis.jaxpr_audit import (CollectiveCensus, DtypePromotionDrift,
                                        NoHbmIntermediate, check_fused_uplink,
                                        collective_census, hbm_elems)
from repro.analysis.repolint import (ALLOWLIST, SpecsComplete, lint_source,
                                     run_repolint)


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

def test_report_exit_codes_and_render():
    ok = report([], checks=3)
    assert ok.ok and ok.exit_code() == 0 and "OK: 3 checks" in ok.render()
    f = Finding(rule="r", where="w", message="m")
    bad = report([f], checks=1)
    assert not bad.ok and bad.exit_code() == 1
    note = Finding(rule="r", where="w", message="m", severity="info")
    advisory = report([note], checks=1)
    assert advisory.ok and advisory.exit_code() == 0
    merged = merge([ok, bad, advisory])
    assert merged.checks == 5 and len(merged.findings) == 2
    assert not merged.ok


def test_finding_rejects_unknown_severity():
    with pytest.raises(AssertionError):
        Finding(rule="r", where="w", message="m", severity="warning")


# ---------------------------------------------------------------------------
# jaxpr walker descent
# ---------------------------------------------------------------------------

def test_walker_descends_custom_vjp():
    """Regression for the old hbm_elems blind spot: an int8 intermediate
    hidden inside a jax.custom_vjp body must still be counted."""
    @jax.custom_vjp
    def f(x):
        v = jnp.where(x > 0, 1, -1).astype(jnp.int8)   # hidden int8 tensor
        return x * v.astype(jnp.float32)

    def fwd(x):
        return f(x), jnp.sign(x)

    def bwd(res, g):
        return (g * res,)

    f.defvjp(fwd, bwd)
    x = jnp.ones((256,), jnp.float32)
    assert hbm_elems(f, x, dtype=jnp.int8) >= 256


@pytest.mark.parametrize("n", [63, 256, 1000])
def test_walker_descends_scan_while_pjit(n):
    """int8 tensors inside scan and while bodies, under a jit (pjit eqn),
    are all visible to the walker — for any leaf size."""
    @jax.jit
    def prog(x):
        def sbody(c, _):
            t = jnp.sign(c).astype(jnp.int8)
            return c + t.astype(jnp.float32), t
        c, ts = jax.lax.scan(sbody, x, None, length=3)

        def wcond(s):
            return s[1] < 2

        def wbody(s):
            y, i = s
            u = jnp.sign(y).astype(jnp.int8)
            return y + u.astype(jnp.float32), i + 1

        y, _ = jax.lax.while_loop(wcond, wbody, (c, 0))
        return y + ts.astype(jnp.float32).sum(0)

    x = jnp.ones((n,), jnp.float32)
    assert hbm_elems(prog, x, dtype=jnp.int8) >= 2 * n


def test_walker_excludes_pallas_body():
    """int8 values inside a pallas_call kernel body live in VMEM registers,
    not HBM — the walker must not count them."""
    import jax.experimental.pallas as pl

    def kern(x_ref, o_ref):
        t = x_ref[...].astype(jnp.int8)
        o_ref[...] = t.astype(jnp.float32)

    def op(x):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True)(x)

    x = jnp.ones((8, 128), jnp.float32)
    assert hbm_elems(op, x, dtype=jnp.int8) == 0


def test_no_hbm_intermediate_limit_semantics():
    rule0 = NoHbmIntermediate(jnp.int8)
    rule_n = NoHbmIntermediate(jnp.int8, limit=128)
    fn = lambda x: jnp.sign(x).astype(jnp.int8).astype(jnp.float32)
    x = jnp.ones((128,), jnp.float32)
    assert len(rule0.check("lab", fn, x)) == 1        # 128 > 0
    assert rule_n.check("lab", fn, x) == []           # 128 <= 128


# ---------------------------------------------------------------------------
# collective census byte math (synthetic shard_map program)
# ---------------------------------------------------------------------------

def test_census_byte_math_on_shard_map_program():
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    P = jax.sharding.PartitionSpec
    n = 1024

    def body(v, s):
        tot = jax.lax.psum(v, ("data",))                       # int8 payload
        mx = jax.lax.pmax(s, ("data",))                        # f32 scalar
        g = jax.lax.all_gather(v, ("data",), axis=0, tiled=False)
        return tot, mx, g

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.zeros((n,), jnp.int8),
                                jnp.zeros((), jnp.float32))
    census = collective_census(closed)
    assert census.counts() == {"psum": 1, "pmax": 1, "all_gather": 1}
    m = 8
    sizes = {"data": m}
    # psum all-reduce 2(m-1)/m * n B + all-gather (m-1) * n B
    assert census.payload_bytes(sizes) == pytest.approx(
        2 * (m - 1) / m * n + (m - 1) * n)
    assert census.scalar_bytes(sizes) == pytest.approx(2 * (m - 1) / m * 4)
    # degenerate group: every ring term vanishes
    assert census.total_bytes({"data": 1}) == 0.0

    rule = CollectiveCensus(axis_sizes=sizes)
    ok = rule.check("prog", census,
                    ledger_payload=2 * (m - 1) / m * n + (m - 1) * n,
                    ledger_scalar_min=2 * (m - 1) / m * 4)
    assert ok == []
    bad = rule.check("prog", census, ledger_payload=12345.0,
                     ledger_scalar_min=1e9)
    assert len(bad) == 2


# ---------------------------------------------------------------------------
# ppermute ring math, unknown-collective loudness, gather-HBM floor
# ---------------------------------------------------------------------------

def test_census_ppermute_ring_math():
    """ONE traced ppermute (the ring gather's hop primitive, while-looped at
    trips=1) bills as an (M-1)-hop ring of its operand."""
    from repro.analysis.jaxpr_audit import CollectiveRecord
    from repro.dist import collectives
    from repro.launch.mesh import make_host_mesh

    rec = CollectiveRecord(primitive="ppermute", axes=("data",),
                           in_elems=2048, in_bytes=2048, out_bytes=2048)
    assert rec.ring_bytes({"data": 16}) == pytest.approx(15 * 2048)
    assert rec.ring_bytes({"data": 1}) == 0.0

    # and the traced program agrees: the sanctioned wrapper emits exactly one
    # ppermute eqn, billed at (m-1) x operand bytes
    mesh = make_host_mesh(1, 1)
    P = jax.sharding.PartitionSpec
    n = 2048
    fn = jax.shard_map(lambda v: collectives.ring_permute(v, ("data",)),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    census = collective_census(jax.make_jaxpr(fn)(jnp.zeros((n,), jnp.int8)))
    assert census.counts() == {"ppermute": 1}
    assert census.payload_bytes({"data": 16}) == pytest.approx(15 * n)
    assert census.total_bytes({"data": 1}) == 0.0


def test_census_unknown_collective_blocks():
    """A payload-carrying named-axis equation the byte model does not cover
    must surface as a blocking finding — never a silent zero-byte bill."""
    from repro.analysis.jaxpr_audit import Census, CollectiveRecord

    mystery = CollectiveRecord(primitive="all_to_all_v", axes=("data",),
                               in_elems=512, in_bytes=512, out_bytes=512)
    census = Census(records=(), unknown=(mystery,))
    rule = CollectiveCensus(axis_sizes={"data": 16})
    findings = rule.check("prog", census, ledger_payload=0.0)
    assert any("does not cover" in f.message and "all_to_all_v" in f.message
               for f in findings)
    assert all(f.severity == "error" for f in findings)
    # unknowns are excluded from every byte sum — that's WHY the rule blocks
    assert census.payload_bytes({"data": 16}) == 0.0


def test_gather_hbm_budget_math():
    from repro.analysis.jaxpr_audit import GatherHbmBudget

    rule = GatherHbmBudget(min_ratio=8.0)
    # monolithic M x payload vs a 2-chunk ring at M=16: ratio 8x, at the floor
    assert rule.check("x", ring_bytes=2 * 4096.0,
                      mono_bytes=16 * 4096.0) == []
    bad = rule.check("x", ring_bytes=3 * 4096.0, mono_bytes=16 * 4096.0)
    assert len(bad) == 1 and "under the 8.0x floor" in bad[0].message


def test_gather_hbm_checks_green():
    """The blocking M/2 peak-HBM floor holds on every stacked-block config,
    every ring setup, per-leaf and bucketed — the acceptance criterion."""
    findings, checks = drivers.gather_hbm_checks()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert checks == len(drivers.RATIO_CONFIGS) * len(drivers.RING_SETUPS) * 2


# ---------------------------------------------------------------------------
# the acceptance pin: step census == VoteWire ledger, all wire modes
# (monolithic AND ring-pipelined exchange strategies)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode",
                         list(drivers.MODE_SETUPS) + list(drivers.RING_SETUPS))
def test_step_census_matches_wire_ledger(mode):
    findings, census, payload, scalar = drivers.census_check(mode)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert payload > 0  # non-vacuous: the hypothetical-M ring terms are real
    assert census.payload_bytes({"data": drivers.HYPOTHETICAL_M}) == \
        pytest.approx(payload)


# ---------------------------------------------------------------------------
# per-spec fused-uplink rules + dtype promotion drift
# ---------------------------------------------------------------------------

def test_every_fused_spec_passes_its_declared_hbm_rules():
    from repro.core.compressors import SPECS
    g = jnp.asarray(np.random.RandomState(3).randn(2048), jnp.float32)
    ran = 0
    for spec in SPECS.values():
        if spec.fused_pack_op is None:
            continue
        assert check_fused_uplink(spec, g) == [], spec.name
        ran += 1
    assert ran >= 5  # all ternary fused rows + qsgd8


def test_dtype_promotion_drift_flags_f32_on_bf16_path():
    drift = DtypePromotionDrift()
    g16 = jnp.asarray(np.random.RandomState(4).randn(256), jnp.bfloat16)
    # the jnp reference path round-trips the whole leaf through f32: flagged
    bad = drift.check("ref", lambda x: jnp.sign(
        x.astype(jnp.float32)).astype(jnp.int8), g16)
    assert len(bad) == 1 and "float32" in bad[0].message
    # the fused kernel keeps f32 math in VMEM registers: clean
    from repro.core.compressors import get_spec
    spec = get_spec("sparsign")
    good = drift.check("fused", lambda x: spec.fused_pack_op(
        x, 1.0, jnp.uint32(7), interpret=True), g16)
    assert good == [], "\n".join(f.render() for f in good)


# ---------------------------------------------------------------------------
# HLO pass: synthetic-HLO parser math + agreement tolerance
# ---------------------------------------------------------------------------

def test_hlo_parser_ring_math_synthetic():
    from repro.launch.hlo_stats import parse_collectives
    hlo = """
  %ar = s8[1024] all-reduce(s8[1024] %x), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ag = u8[8,256] all-gather(u8[256] %y), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
"""
    stats = parse_collectives(hlo)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1}
    # ring models: 2*(8-1)/8*1024 and (8-1)/8*2048 == (8-1)*256
    assert stats.wire_bytes == pytest.approx(2 * 7 / 8 * 1024 + 7 * 256)


def test_hlo_jaxpr_agreement_tolerance():
    rule = HloJaxprAgreement(tolerance=0.05)
    assert rule.check("x", hlo_bytes=104.0, jaxpr_bytes=100.0,
                      ledger_bytes=100.0) == []
    bad = rule.check("x", hlo_bytes=120.0, jaxpr_bytes=100.0,
                     ledger_bytes=100.0)
    assert len(bad) == 2
    # 1-device degenerate case: all sides zero, trivially agree
    assert rule.check("x", hlo_bytes=0.0, jaxpr_bytes=0.0,
                      ledger_bytes=0.0) == []


def test_hlo_check_on_built_step():
    findings, checks = drivers.hlo_check("votes")
    assert checks == 1
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# AST repo-lint: unit cases via lint_source
# ---------------------------------------------------------------------------

def test_lint_flags_compressor_name_branching():
    src = "def f(cfg):\n    if cfg.compressor == 'sparsign':\n        return 1\n"
    hits = lint_source(src, "repro/train/foo.py")
    assert [f.rule for f in hits] == ["no-compressor-name-branching"]
    # membership test counts too
    src = "def f(algorithm):\n    return algorithm in ('sign', 'terngrad')\n"
    assert len(lint_source(src, "repro/train/foo.py")) == 1
    # prefix dispatch counts too
    src = "def f(cfg):\n    return cfg.compressor.startswith('qsgd')\n"
    assert len(lint_source(src, "repro/train/foo.py")) == 1


def test_lint_name_branching_negatives():
    # non-compressor identifiers comparing against a spec-name string: fine
    src = "def f(mode):\n    return mode == 'sign'\n"
    assert lint_source(src, "repro/train/foo.py") == []
    # spec capability lookup: fine
    src = "def f(spec):\n    return spec.wire_format == 'pack2'\n"
    assert lint_source(src, "repro/train/foo.py") == []
    # the registry module itself is exempt — names are DEFINED there
    src = "def g(compressor):\n    return compressor == 'sparsign'\n"
    assert lint_source(src, "repro/core/compressors.py") == []


def test_lint_flags_raw_collectives():
    src = "import jax\ndef f(x):\n    return jax.lax.psum(x, 'data')\n"
    hits = lint_source(src, "repro/train/foo.py")
    assert [f.rule for f in hits] == ["no-raw-collectives"]
    assert lint_source(src, "repro/dist/collectives.py") == []   # the home
    src = "from jax.lax import psum\n"
    assert len(lint_source(src, "repro/train/foo.py")) == 1
    # axis_index moves no payload: allowed anywhere
    src = "import jax\ndef f():\n    return jax.lax.axis_index('data')\n"
    assert lint_source(src, "repro/train/foo.py") == []


def test_lint_flags_jnp_alloc_in_kernel_bodies_only():
    kernel_src = ("import jax.numpy as jnp\n"
                  "def k(x_ref, o_ref):\n"
                  "    t = jnp.zeros((8, 128), jnp.float32)\n"
                  "    o_ref[...] = t\n")
    hits = lint_source(kernel_src, "repro/kernels/foo/kernel.py")
    assert [f.rule for f in hits] == ["no-jnp-alloc-in-kernel"]
    # *_like takes its shape from a Ref operand: kernel-legal
    like_src = ("import jax.numpy as jnp\n"
                "def k(x_ref, o_ref):\n"
                "    o_ref[...] = jnp.zeros_like(o_ref)\n")
    assert lint_source(like_src, "repro/kernels/foo/kernel.py") == []
    # same allocation outside a kernel body / outside kernel.py: fine
    assert lint_source(kernel_src, "repro/kernels/foo/ops.py") == []
    host_src = ("import jax.numpy as jnp\n"
                "def launcher(x):\n"
                "    return jnp.zeros((8,), jnp.float32) + x\n")
    assert lint_source(host_src, "repro/kernels/foo/kernel.py") == []


def test_repolint_repo_green_with_empty_allowlist():
    """The zero-entry allowlist pin: the whole package passes every AST rule
    with NO grandfathered sites."""
    assert len(ALLOWLIST) == 0
    findings, checks = run_repolint()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert checks > 100  # every file x every rule actually ran


def test_specs_complete_rule_green():
    assert SpecsComplete().check() == []


# ---------------------------------------------------------------------------
# encoding bit model is a spec lookup
# ---------------------------------------------------------------------------

def test_baseline_bits_spec_lookup():
    from repro.core.encoding import baseline_bits_per_round, ternary_stream_bits
    d = 100_000
    assert baseline_bits_per_round(d, "scaled_sign") == d
    assert baseline_bits_per_round(d, "noisy_sign") == d
    assert baseline_bits_per_round(d, "terngrad", nnz=500) == pytest.approx(
        ternary_stream_bits(d, 500, coder="golomb") + 32.0)
    assert baseline_bits_per_round(d, "qsgd8") == 8 * d + 32
    with pytest.raises(ValueError):
        baseline_bits_per_round(d, "not_a_compressor")
