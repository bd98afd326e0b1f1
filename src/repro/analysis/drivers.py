"""Shared drivers for the analysis CLI and tests: the tiny-model step builds
whose traced collectives the census/HLO passes pin against the VoteWire ledger.

One definition serves both ``python -m repro.analysis`` and
tests/test_analysis.py, so the blocking CI gate and the tier-1 suite audit the
SAME programs.

Census-at-hypothetical-M mechanics: the step is built and traced on a 1-device
mesh (tier-1 has no multi-device hardware), but the equation *structure* —
which collectives run, over which named axes, with what operand shapes — is
independent of the axis size, so the ring byte model is evaluated at
``HYPOTHETICAL_M`` workers to make every term non-vacuous. Two constraints
make this sound:

  * M <= 127 keeps the hypothetical worker count in the same int8
    ``_sum_dtype`` bucket as the M=1 build, so the traced psum payload dtype
    is the one a real M-worker build would use;
  * the step is built with ``backend="interpret"`` — the jnp backend of the
    gather wires SKIPS the all-gather (it is the fp32-psum oracle program),
    so only the kernel backends trace the honest wire.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.analysis.hlo_audit import HloJaxprAgreement, hlo_collective_stats
from repro.analysis.jaxpr_audit import (CollectiveCensus, CollectiveCountBudget,
                                        DtypePromotionDrift, EntropyWireBudget,
                                        GatherHbmBudget, MaskedPayloadZero,
                                        check_fused_uplink, collective_census)

#: hypothetical worker count the census ring model is costed at: > 1 so every
#: ring term is non-vacuous, <= 127 so the int8 _sum_dtype bucket still holds
HYPOTHETICAL_M = 16

#: plan-time nonzero fraction of the golomb setup: the paper-regime 5%
#: sparsity — doubles as the setup's target_sparsity budget, so
#: ``engine.resolve_golomb_p`` sizes the wire capacity from the SAME number
GOLOMB_P = 0.05

#: wire setup -> (compressor, server, vote_impl, budget): one representative
#: registry row per setup (engine.wire_mode must resolve to
#: ``wire_mode_of(key)``). "golomb" is the entropy-coded PAYLOAD format of
#: the votes mode (engine.wire_payload_format), not a wire mode of its own —
#: it gets its own setup row because its wire object, ledger arithmetic and
#: bucket plan all differ from the flat 2-bit votes wire.
MODE_SETUPS = {
    "votes": ("sparsign", "majority_vote", "psum", 2.0),
    "scaled_votes": ("terngrad", "mean", "psum", 1.0),
    "pack8": ("qsgd8", "mean", "allgather_packed", 1.0),
    "decoded": ("qsgd8", "mean", "psum", 1.0),
    "golomb": ("sparsign_golomb", "majority_vote", "allgather_packed",
               GOLOMB_P),
}

#: chunk size (payload rows) the ring setups sweep with: deliberately tiny —
#: one sublane tile — so the tiny-model BUCKETED plans split into many chunks
#: and the census sees a genuinely multi-chunk ring (at the production
#: default of collectives.DEFAULT_RING_CHUNK_ROWS the tiny model would be
#: one chunk everywhere and the chunk loop would go untested)
RING_SWEEP_CHUNK_ROWS = 32

#: ring-gather setups: the three gather wires again, exchanged over the
#: chunked ppermute ring instead of the monolithic all_gather. Kept in their
#: own table (not MODE_SETUPS) so the monolithic pins keep their exact
#: parametrization; every census/count driver sweeps both tables.
RING_SETUPS = {
    "ring_pack2": ("sparsign", "majority_vote", "allgather_packed", 2.0),
    "ring_pack8": ("qsgd8", "mean", "allgather_packed", 1.0),
    "ring_golomb": ("sparsign_golomb", "majority_vote", "allgather_packed",
                    GOLOMB_P),
}


def _setup_of(mode: str) -> tuple:
    """(compressor, server, vote_impl, budget) row of either setup table."""
    return MODE_SETUPS[mode] if mode in MODE_SETUPS else RING_SETUPS[mode]


def wire_mode_of(mode: str) -> str:
    """The engine wire mode one setup's negotiation resolves to — identity
    except for the golomb setups (which ride the votes mode on an
    entropy-coded payload) and the ring setups (the ring is an exchange
    strategy of the SAME wire modes, not a mode of its own)."""
    if mode.endswith("golomb") or mode == "ring_pack2":
        return "votes"
    if mode == "ring_pack8":
        return "pack8"
    return mode


def tiny_model():
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.models.model import Model
    cfg = ModelConfig(name="analysis-tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=64, pattern=(LayerSpec(mixer="attn"),),
                      dtype="float32", attn_chunk=8, q_chunk=8, loss_chunk=8,
                      remat=False)
    return Model(cfg)


def tiny_batch(vocab: int, b: int = 2, s: int = 8, seed: int = 0):
    import numpy as np
    rng = np.random.RandomState(seed)
    return {
        "inputs": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }


def mode_comp(mode: str):
    """The representative CompressionConfig of one wire mode."""
    from repro.core.algorithm import CompressionConfig
    from repro.core.budgets import BudgetConfig

    compressor, server, vote_impl, budget = _setup_of(mode)
    # the golomb setups' budget IS their plan sparsity: a target_sparsity
    # budget both drives the compressor and resolves the wire capacity p
    kind = "target_sparsity" if mode.endswith("golomb") else "fixed"
    return CompressionConfig(compressor=compressor,
                             budget=BudgetConfig(kind=kind, value=budget),
                             server=server)


def participation_spec():
    """The ParticipationSpec the elastic setups build with: uniform weights,
    the quorum as an explicit fraction. The census/count billing depends only
    on the spec's PRESENCE (which exchange family the step traces), not on
    its numbers — any valid spec pins the same equations."""
    from repro.dist import collectives
    return collectives.ParticipationSpec(q_frac=0.5)


def mode_wire(mode: str, m: int, *, elastic: bool = False):
    """A costing-only VoteWire at hypothetical worker count ``m`` — the ring
    setups cost (and the steps build) their wires with the sweep chunk size.
    ``elastic=True`` attaches the participation spec, switching the byte
    ledger to the weighted-exchange billing (psum wires: two f32 all-reduces;
    gather wires: the weight side channel)."""
    from repro.dist import collectives

    part = participation_spec() if elastic else None
    rcr = RING_SWEEP_CHUNK_ROWS if mode in RING_SETUPS else None
    if mode == "pack8" or mode == "ring_pack8":
        return collectives.Pack8Wire(axes=("data",), n_workers=m,
                                     ring_chunk_rows=rcr, participation=part)
    if mode.endswith("golomb"):
        return collectives.GolombWire(axes=("data",), n_workers=m, p=GOLOMB_P,
                                      ring_chunk_rows=rcr, participation=part)
    if mode == "ring_pack2":
        return collectives.PackedVoteWire(axes=("data",), n_workers=m,
                                          ring_chunk_rows=rcr,
                                          participation=part)
    return collectives.VoteWire(axes=("data",), n_workers=m,
                                participation=part)


def build_mode_step(mode: str, *, bucketed: bool = False,
                    elastic: bool = False, participation=None):
    """Build the 1-device `simple` train step whose wire negotiation resolves
    to ``mode``; returns (step, state, batch, model, mesh, comp).
    ``elastic=True`` builds the weighted, participation-normalized variant
    (the same ParticipationSpec as ``mode_wire(elastic=True)``); an explicit
    ``participation`` spec overrides it (the bench's chaos timing rows)."""
    from repro.core import engine
    from repro.launch.mesh import make_host_mesh
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step

    _, server, vote_impl, _ = _setup_of(mode)
    comp = mode_comp(mode)
    resolved = engine.wire_mode(comp, vote_impl=vote_impl)
    assert resolved == wire_mode_of(mode), (mode, resolved)
    if mode.endswith("golomb"):
        # the golomb setups are only themselves if the payload negotiation
        # picks the entropy-coded stream (votes mode + the gather impl)
        assert engine.wire_payload_format(
            comp, resolved, vote_impl=vote_impl) == "golomb"
    model = tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = tiny_batch(model.cfg.vocab_size)
    scfg = TrainStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                           worker_axes=("data",), vote_impl=vote_impl,
                           donate=False, backend="interpret",
                           bucketed=bucketed,
                           ring_chunk_rows=(RING_SWEEP_CHUNK_ROWS
                                            if mode in RING_SETUPS else None),
                           participation=(participation
                                          if participation is not None
                                          else (participation_spec()
                                                if elastic else None)))
    step = build_train_step(model, scfg, mesh)
    state = init_state(params, server=server, seed=7)
    return step, state, batch, model, mesh, comp


def mode_ledger(mode: str, model, m: int):
    """(payload_bytes, scalar_bytes) the VoteWire ledger bills for one round
    of the tiny model at a hypothetical worker count ``m`` — split the way the
    census splits (array payloads vs protocol scalars). The split re-sums to
    ``collectives.uplink_ledger`` exactly (asserted per leaf)."""
    from repro.core import engine
    from repro.dist import collectives

    comp = mode_comp(mode)
    share = engine.needs_shared_linf(comp)
    wire = mode_wire(mode, m)
    emode = wire_mode_of(mode)
    payload = scalar = 0.0
    for s in jax.tree_util.tree_leaves(model.param_shapes()):
        n = int(math.prod(s.shape))
        p = (collectives.decoded_wire_bytes(n, m) if mode == "decoded"
             else wire.wire_bytes(n))
        # pack8 decode scales ride once per ring chunk (x1 monolithic)
        sc = (wire.scalar_bytes() * wire.ring_chunks(n)
              if emode == "pack8" else 0.0) \
            + (collectives.allreduce_scalar_bytes(m) if share else 0.0)
        assert abs((p + sc) - collectives.uplink_ledger(
            emode, wire, n, share_linf=share)) < 1e-6, (mode, n)
        payload += p
        scalar += sc
    return payload, scalar


def mode_bucket_plan(mode: str, model, m: int, bucket_bytes=None):
    """The BucketPlan the bucketed simple step builds for ``model``."""
    from repro.dist import bucketing

    wire = mode_wire(mode, m)
    fmt = bucketing.wire_bucket_format(wire_mode_of(mode), wire)
    return bucketing.build_bucket_plan(
        jax.tree_util.tree_leaves(model.param_shapes()), fmt,
        bucket_bytes=bucket_bytes,
        rows_fn=(wire.payload_rows if fmt == "golomb" else None))


def mode_bucketed_ledger(mode: str, model, m: int, bucket_bytes=None, *,
                         elastic: bool = False):
    """(payload_bytes, scalar_bytes, plan) the bucketed-wire ledger bills for
    one round of ``model`` at ``m`` hypothetical workers — the bucketed twin
    of ``mode_ledger``, split the same census way. ``elastic=True`` bills the
    participation-carrying wire (``uplink_ledger_bucket`` reads the spec off
    the wire: the pack8 side vector widens by the raw-weight entry, the
    ternary gather wires add the (1,) weight scalar, the psum wires' second
    f32 participation all-reduce lands inside the payload term)."""
    from repro.core import engine
    from repro.dist import bucketing

    share = engine.needs_shared_linf(mode_comp(mode))
    wire = mode_wire(mode, m, elastic=elastic)
    plan = mode_bucket_plan(mode, model, m, bucket_bytes)
    payload, scalar = bucketing.plan_ledger(wire_mode_of(mode), wire, plan,
                                            share_linf=share)
    return payload, scalar, plan


def elastic_mode_ledger(mode: str, model, m: int):
    """(payload_bytes, scalar_bytes) the per-leaf ELASTIC wire bills for one
    round at ``m`` hypothetical workers — the weighted-exchange twin of
    ``mode_ledger``, split the census way: the psum wires' participation
    all-reduce is a second per-coordinate f32 payload (inside
    ``wire_bytes``), pack8's widened [scale*w, w] side slot is a (2,) gather
    — >= 2 elements, payload class — and the ternary gather wires' (1,)
    weight is scalar protocol traffic. Re-sums to ``uplink_ledger``
    exactly (asserted per leaf); the decoded mode bypasses the wire object
    (weights premultiply the decode scale), so nothing widens there."""
    from repro.core import engine
    from repro.dist import collectives

    comp = mode_comp(mode)
    share = engine.needs_shared_linf(comp)
    wire = mode_wire(mode, m, elastic=True)
    emode = wire_mode_of(mode)
    payload = scalar = 0.0
    for s in jax.tree_util.tree_leaves(model.param_shapes()):
        n = int(math.prod(s.shape))
        p = (collectives.decoded_wire_bytes(n, m) if mode == "decoded"
             else wire.wire_bytes(n))
        sc = 0.0
        if emode == "pack8":
            p += wire.scalar_bytes() * wire.ring_chunks(n)
        elif mode != "decoded":
            sc += wire.weight_bytes() * wire.ring_chunks(n)
        if share:
            sc += collectives.allreduce_scalar_bytes(m)
        assert abs((p + sc) - collectives.uplink_ledger(
            emode, wire, n, share_linf=share)) < 1e-6, (mode, n)
        payload += p
        scalar += sc
    return payload, scalar


def traced_step_census(mode: str, *, bucketed: bool = False):
    """Trace the mode's built step and census its collectives. Returns
    (census, model)."""

    step, state, batch, model, mesh, _ = build_mode_step(mode, bucketed=bucketed)
    with jax.sharding.set_mesh(mesh):
        closed = jax.make_jaxpr(step)(state, batch)
    return collective_census(closed), model


def census_check(mode: str, m: int = HYPOTHETICAL_M, *, bucketed: bool = False):
    """The acceptance pin: traced collective array-payload bytes == VoteWire
    ledger bytes at ``m`` hypothetical workers, scalar traffic covers the
    protocol scalars. ``bucketed=True`` pins the bucketed step against the
    ``bucketing.plan_ledger`` twin instead. Returns
    (findings, census, ledger_payload, ledger_scalar)."""
    census, model = traced_step_census(mode, bucketed=bucketed)
    if bucketed:
        payload, scalar, _ = mode_bucketed_ledger(mode, model, m)
    else:
        payload, scalar = mode_ledger(mode, model, m)
    rule = CollectiveCensus(axis_sizes={"data": m})
    label = f"step[{mode}{'/bucketed' if bucketed else ''}]"
    findings = rule.check(label, census,
                          ledger_payload=payload, ledger_scalar_min=scalar)
    return findings, census, payload, scalar


def run_census_checks(m: int = HYPOTHETICAL_M):
    findings, checks = [], 0
    for mode in list(MODE_SETUPS) + list(RING_SETUPS):
        for bucketed in (False, True):
            f, _, _, _ = census_check(mode, m, bucketed=bucketed)
            findings += f
            checks += 1
    return findings, checks


# ---------------------------------------------------------------------------
# Collective LAUNCH counts — the bucketed wire's raison d'etre
# ---------------------------------------------------------------------------

def mode_count_budget(mode: str, model, *, bucketed: bool,
                      m: int = HYPOTHETICAL_M):
    """(expected_payload_launches, max_scalar_launches) for one simple-mode
    round. Per-leaf: one payload exchange per leaf. Bucketed: one per bucket,
    plus one (n_slots,) scale-vector gather on the pack8 wire and one (L,)
    shared-linf pmax when the compressor shares its scale — both >= 2
    elements, so they count as payload launches (and are billed as payload
    bytes by the same rule in ``plan_ledger``). Ring setups launch one
    payload ppermute per CHUNK (the wire's ``ring_chunks`` framing), and
    the ring pack8 bucket re-ships its scale vector with every chunk."""
    from repro.core import engine

    leaves = jax.tree_util.tree_leaves(model.param_shapes())
    n_leaves = len(leaves)
    share = engine.needs_shared_linf(mode_comp(mode))
    wire = mode_wire(mode, m)
    if not bucketed:
        # scalar budget: per-leaf n_sel (+ per-leaf scale protocol on the
        # shared/pack8 wires, once per ring chunk) + metric reductions
        expected = sum(wire.ring_chunks(int(math.prod(s.shape)))
                       for s in leaves)
        return expected, n_leaves + expected + 8
    plan = mode_bucket_plan(mode, model, m)
    if mode in RING_SETUPS:
        chunks = sum(wire.bucket_ring_chunks(b) for b in plan.buckets)
        extra = (chunks if wire_mode_of(mode) == "pack8" else 0) \
            + (1 if share else 0)
        return chunks + extra, 8
    extra = (1 if mode == "pack8" else 0) + (1 if share else 0)
    return len(plan.buckets) + extra, 8


def count_check(mode: str, *, bucketed: bool):
    """Blocking launch-count pin: traced payload-collective launches ==
    the mode budget exactly; scalar launches under the protocol cap."""
    census, model = traced_step_census(mode, bucketed=bucketed)
    expected, max_scalar = mode_count_budget(mode, model, bucketed=bucketed)
    rule = CollectiveCountBudget()
    label = f"step[{mode}{'/bucketed' if bucketed else ''}]"
    return rule.check(label, census, expected_payload=expected,
                      max_scalar=max_scalar), census, expected


def elastic_count_budget(mode: str, model, *, bucketed: bool,
                         m: int = HYPOTHETICAL_M):
    """(expected_payload_launches, max_scalar_launches) of the ELASTIC step:
    the psum wires launch TWO f32 all-reduces per exchange (weighted vote +
    per-coordinate participation count), pack8 gathers its widened
    >= 2-element side vector next to every payload, the ternary gather wires
    add only a (1,) scalar weight gather, and decoded keeps its single psum
    (weights premultiply the decode scale before the reduce). The scalar cap
    widens over the legacy budget for the per-leaf weight gathers / the
    decoded mode's per-leaf participation psums."""
    from repro.core import engine

    leaves = jax.tree_util.tree_leaves(model.param_shapes())
    n_leaves = len(leaves)
    share = engine.needs_shared_linf(mode_comp(mode))
    _, _, vote_impl, _ = _setup_of(mode)
    if mode == "decoded":
        per = 1                 # one f32 psum; W is a scalar psum
    elif wire_mode_of(mode) == "pack8":
        per = 2                 # payload gather + (n_side >= 2,) side gather
    elif vote_impl == "psum":
        per = 2                 # weighted-vote psum + participation psum
    else:
        per = 1                 # ternary gather; the (1,) weight is scalar
    if not bucketed:
        return per * n_leaves, 3 * n_leaves + 8
    plan = mode_bucket_plan(mode, model, m)
    extra = 1 if share else 0   # the (L,) shared-linf pmax
    return per * len(plan.buckets) + extra, len(plan.buckets) + 8


def run_participation_checks(m: int = HYPOTHETICAL_M):
    """The elastic-participation gate: trace the ELASTIC build of every
    wire-mode setup (per-leaf AND bucketed) once, and run three blocking
    rules on the same jaxpr — the census byte pin against the elastic
    ledger, the launch-count pin against the elastic budget, and the
    masked-payload-zero rule (every untiled integer gather payload must
    trace back to its participation mask). The legacy ring setups get the
    mask rule too: the chunked ppermute hop ships the same masked buffers,
    and the cross-scope backtrack (while-carry -> init operand) is exactly
    what the ring exercises."""

    findings, checks = [], 0
    census_rule = CollectiveCensus(axis_sizes={"data": m})
    count_rule = CollectiveCountBudget()
    mask_rule = MaskedPayloadZero()
    for mode in MODE_SETUPS:
        for bucketed in (False, True):
            step, state, batch, model, mesh, _ = build_mode_step(
                mode, bucketed=bucketed, elastic=True)
            with jax.sharding.set_mesh(mesh):
                closed = jax.make_jaxpr(step)(state, batch)
            census = collective_census(closed)
            label = f"step[{mode}{'/bucketed' if bucketed else ''}/elastic]"
            if bucketed:
                payload, scalar, _ = mode_bucketed_ledger(mode, model, m,
                                                          elastic=True)
            else:
                payload, scalar = elastic_mode_ledger(mode, model, m)
            findings += census_rule.check(label, census,
                                          ledger_payload=payload,
                                          ledger_scalar_min=scalar)
            expected, max_scalar = elastic_count_budget(mode, model,
                                                        bucketed=bucketed,
                                                        m=m)
            findings += count_rule.check(label, census,
                                         expected_payload=expected,
                                         max_scalar=max_scalar)
            findings += mask_rule.check(label, closed)
            checks += 3
    for mode in RING_SETUPS:
        step, state, batch, model, mesh, _ = build_mode_step(mode,
                                                             bucketed=True)
        with jax.sharding.set_mesh(mesh):
            closed = jax.make_jaxpr(step)(state, batch)
        findings += mask_rule.check(f"step[{mode}/bucketed]", closed)
        checks += 1
    return findings, checks


#: stacked-block model configs the launch-ratio floor is asserted on
RATIO_CONFIGS = ("qwen1.5-4b", "qwen2.5-32b", "qwen2-moe-a2.7b")

#: per-leaf / bucketed payload-launch floor on every stacked-block config
MIN_COUNT_RATIO = 5.0


def count_ratio_checks(m: int = HYPOTHETICAL_M):
    """Static acceptance floor: on every stacked-block model config, the
    bucketed wire must launch >= MIN_COUNT_RATIO x fewer payload collectives
    than the per-leaf wire, for every mode. Pure plan arithmetic — no big
    model is traced, only its shape tree."""
    from repro.configs.registry import get_config
    from repro.models.model import Model

    rule = CollectiveCountBudget()
    findings, checks = [], 0
    for name in RATIO_CONFIGS:
        model = Model(get_config(name))
        for mode in MODE_SETUPS:
            per_leaf, _ = mode_count_budget(mode, model, bucketed=False)
            bucketed, _ = mode_count_budget(mode, model, bucketed=True)
            checks += 1
            if per_leaf < MIN_COUNT_RATIO * bucketed:
                findings.append(rule.finding(
                    f"{name}[{mode}]",
                    f"bucketed wire launches {bucketed} payload collectives "
                    f"vs {per_leaf} per-leaf — ratio "
                    f"{per_leaf / max(bucketed, 1):.1f}x is under the "
                    f"{MIN_COUNT_RATIO:.0f}x floor"))
    return findings, checks


def run_count_checks():
    findings, checks = [], 0
    for mode in list(MODE_SETUPS) + list(RING_SETUPS):
        for bucketed in (False, True):
            f, _, _ = count_check(mode, bucketed=bucketed)
            findings += f
            checks += 1
    # count_ratio_checks stays on the monolithic setups: the ring trades
    # launch count for residency BY DESIGN (one ppermute per chunk), so a
    # bucketed-vs-per-leaf launch floor is the wrong question there —
    # gather_hbm_checks asserts the ring's own win instead
    f, c = count_ratio_checks()
    return findings + f, checks + c


#: billed-byte floor of the entropy-coded wire vs the flat 2-bit wire at the
#: paper-regime plan sparsity (the PR's acceptance threshold)
MIN_ENTROPY_RATIO = 2.0


def entropy_wire_ledgers(model, m: int = HYPOTHETICAL_M):
    """((golomb_per_leaf, pack2_per_leaf), (golomb_bucketed, pack2_bucketed))
    payload bytes one round of ``model`` bills on the entropy-coded wire vs
    the flat 2-bit gather wire at ``m`` hypothetical workers. Pure ledger/plan
    arithmetic — no tracing; the same formulas the census pins bytes against,
    so a floor asserted here is a floor on the traced wire."""
    from repro.dist import bucketing, collectives

    gw = mode_wire("golomb", m)
    pw = collectives.PackedVoteWire(axes=("data",), n_workers=m)
    leaves = jax.tree_util.tree_leaves(model.param_shapes())
    g_leaf = sum(gw.wire_bytes(int(math.prod(s.shape))) for s in leaves)
    p_leaf = sum(pw.wire_bytes(int(math.prod(s.shape))) for s in leaves)
    g_plan = bucketing.build_bucket_plan(leaves, "golomb",
                                         rows_fn=gw.payload_rows)
    p_plan = bucketing.build_bucket_plan(leaves, "pack2")
    g_bucket, _ = bucketing.plan_ledger("votes", gw, g_plan)
    p_bucket, _ = bucketing.plan_ledger("votes", pw, p_plan)
    return (g_leaf, p_leaf), (g_bucket, p_bucket)


def entropy_wire_checks(m: int = HYPOTHETICAL_M):
    """Blocking byte-ratio floor: on every stacked-block model config, the
    golomb wire's billed payload bytes — capacity padding tax included — must
    undercut the flat 2-bit wire by >= MIN_ENTROPY_RATIO x at the paper-regime
    plan sparsity (GOLOMB_P), per-leaf AND bucketed. The byte twin of
    ``count_ratio_checks``: pure plan arithmetic over the real model shape
    trees, no tracing."""
    from repro.configs.registry import get_config
    from repro.models.model import Model

    rule = EntropyWireBudget(MIN_ENTROPY_RATIO)
    findings, checks = [], 0
    for name in RATIO_CONFIGS:
        model = Model(get_config(name))
        (g_leaf, p_leaf), (g_bucket, p_bucket) = entropy_wire_ledgers(model, m)
        findings += rule.check(f"{name}[per-leaf]",
                               golomb_bytes=g_leaf, pack2_bytes=p_leaf)
        findings += rule.check(f"{name}[bucketed]",
                               golomb_bytes=g_bucket, pack2_bytes=p_bucket)
        checks += 2
    return findings, checks


def _ring_wire_pair(mode: str, m: int, chunk_rows: int):
    """(monolithic, ring) twins of one ring setup's gather wire — identical
    wire class and parameters, only the exchange strategy differs."""
    from repro.dist import collectives

    if mode == "ring_pack8":
        cls, kw = collectives.Pack8Wire, {}
    elif mode == "ring_golomb":
        cls, kw = collectives.GolombWire, {"p": GOLOMB_P}
    else:
        cls, kw = collectives.PackedVoteWire, {}
    mono = cls(axes=("data",), n_workers=m, **kw)
    ring = cls(axes=("data",), n_workers=m, ring_chunk_rows=chunk_rows, **kw)
    return mono, ring


def gather_hbm_checks(m: int = HYPOTHETICAL_M):
    """Blocking peak-HBM floor: on every stacked-block model config, the ring
    gather's peak gathered-payload HBM (``gather_hbm_bytes``, at the
    documented production chunk size) must undercut the monolithic gather's
    M x payload by >= M/2 x for every ring setup, per-leaf AND bucketed.
    Pure ledger/plan arithmetic over the real model shape trees — the same
    formulas the train metric surfaces, so a floor here is a floor on the
    reported residency. M/2 is exact for the single-chunk golomb leaf stream
    (2 chunks vs M payloads of the same stream); every chunked case clears
    it with room."""
    from repro.configs.registry import get_config
    from repro.dist import bucketing, collectives
    from repro.models.model import Model

    rule = GatherHbmBudget(min_ratio=m / 2.0)
    findings, checks = [], 0
    for name in RATIO_CONFIGS:
        model = Model(get_config(name))
        leaves = jax.tree_util.tree_leaves(model.param_shapes())
        sizes = [int(math.prod(s.shape)) for s in leaves]
        for mode in RING_SETUPS:
            mono, ring = _ring_wire_pair(
                mode, m, collectives.DEFAULT_RING_CHUNK_ROWS)
            emode = wire_mode_of(mode)
            findings += rule.check(
                f"{name}[{mode}/per-leaf]",
                ring_bytes=max(ring.gather_hbm_bytes(n) for n in sizes),
                mono_bytes=max(mono.gather_hbm_bytes(n) for n in sizes))
            fmt = bucketing.wire_bucket_format(emode, mono)
            plan = bucketing.build_bucket_plan(
                leaves, fmt,
                rows_fn=(mono.payload_rows if fmt == "golomb" else None))
            findings += rule.check(
                f"{name}[{mode}/bucketed]",
                ring_bytes=bucketing.plan_gather_hbm_bytes(emode, ring, plan),
                mono_bytes=bucketing.plan_gather_hbm_bytes(emode, mono, plan))
            checks += 2
    return findings, checks


def hlo_check(mode: str = "votes"):
    """Compile one step and pin the post-SPMD HLO collective bytes against the
    jaxpr census and the ledger at the BUILD worker count. Tier-1 builds on
    one device, where every ring term is zero on all three sides — degenerate
    but honest; the nonzero byte math of the HLO model is pinned by the
    synthetic-HLO tests in tests/test_analysis.py."""

    step, state, batch, model, mesh, _ = build_mode_step(mode)
    with jax.sharding.set_mesh(mesh):
        stats = hlo_collective_stats(step, state, batch, default_group=1)
        closed = jax.make_jaxpr(step)(state, batch)
    census = collective_census(closed)
    m = int(mesh.shape["data"])
    jaxpr_bytes = census.total_bytes({"data": m})
    payload, scalar = mode_ledger(mode, model, m)
    rule = HloJaxprAgreement()
    findings = rule.check(f"hlo[{mode}]", hlo_bytes=stats.wire_bytes,
                          jaxpr_bytes=jaxpr_bytes,
                          ledger_bytes=payload + scalar)
    return findings, 1


def run_spec_checks():
    """Per-registry-row traceable-program rules: every fused wire op against
    its declared ``hbm_limits`` contract (the old hand-written int8/int32 pins,
    now spec-driven), plus the bf16 promotion-drift pin — a declared-bf16
    gradient must reach the wire without a full-size f32 HBM copy."""
    import numpy as np
    from repro.core.compressors import SPECS

    findings, checks = [], 0
    g32 = jnp.asarray(np.random.RandomState(11).randn(4096), jnp.float32)
    g16 = g32.astype(jnp.bfloat16)
    drift = DtypePromotionDrift(banned=("float32",), min_elems=2)
    for spec in SPECS.values():
        if spec.fused_pack_op is None:
            continue
        findings += check_fused_uplink(spec, g32)
        checks += 1
        # param resolved OUTSIDE the traced fn: the scale statistic itself
        # legitimately reads g in f32 — the pin is about the uplink path
        param = spec.local_scale(g16) if spec.local_scale is not None else 1.0
        findings += drift.check(
            f"{spec.name}.fused_pack_op[bf16]",
            lambda x: spec.fused_pack_op(x, param, jnp.uint32(7),
                                         interpret=True), g16)
        checks += 1
    return findings, checks
