"""The bucketized uplink wire (repro.dist.bucketing): static plan invariants,
payload round-trips, the bucketed-vs-per-leaf bitwise equivalence of the simple
train step on every wire mode, per-slot quorum attribution through the bucket,
and the launch-count budgets the analysis gate blocks on.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import drivers
from repro.dist import bucketing, collectives
from repro.kernels import common as kcommon

# odd, tile-hostile shapes on purpose: scalars-adjacent vectors, non-multiple
# of LANES, bf16 leaves
ODD_SHAPES = [
    jax.ShapeDtypeStruct((33,), jnp.float32),
    jax.ShapeDtypeStruct((7, 129), jnp.bfloat16),
    jax.ShapeDtypeStruct((2, 3, 85), jnp.float32),
    jax.ShapeDtypeStruct((513,), jnp.bfloat16),
    jax.ShapeDtypeStruct((64, 511), jnp.float32),
]


# ---------------------------------------------------------------------------
# plan invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", bucketing.BUCKET_FORMATS)
def test_plan_offsets_and_alignment(fmt):
    kw = {}
    wire = None
    if fmt == "golomb":
        # the variable-length format sizes slots by plan-time CAPACITY rows,
        # so the plan needs the wire's rows rule (not a coordinate count)
        wire = collectives.GolombWire(axes=("data",), n_workers=4, p=0.05)
        kw["rows_fn"] = wire.payload_rows
    plan = bucketing.build_bucket_plan(ODD_SHAPES, fmt, **kw)
    align = bucketing.format_align_rows(fmt)
    assert plan.align_rows == align
    seen = []
    for b in plan.buckets:
        row = 0
        for s in b.slots:
            assert s.row_start == row, "slots must be contiguous"
            assert s.row_start % align == 0
            if fmt == "golomb":
                # each slot is one whole self-describing capacity stream
                assert s.rows == wire.payload_rows(s.size)
            else:
                assert s.rows == bucketing.leaf_rows(s.size, align)
                assert s.rows * kcommon.LANES >= s.size
            assert s.size == math.prod(s.shape)
            row += s.rows
            seen.append(s.index)
        # tail padding only for the kernel-decoded packed formats
        if fmt in ("pack2", "pack8"):
            assert b.rows % kcommon.SUBLANE_PAD == 0
            assert b.rows - row < kcommon.SUBLANE_PAD
        else:
            assert b.rows == row
    assert sorted(seen) == list(range(len(ODD_SHAPES)))


def test_plan_golomb_requires_rows_fn():
    with pytest.raises(ValueError, match="rows_fn"):
        bucketing.build_bucket_plan(ODD_SHAPES, "golomb")
    with pytest.raises(ValueError, match="rows_fn"):
        bucketing.build_bucket_plan(ODD_SHAPES, "int8",
                                    rows_fn=lambda n: n)


def test_pack8_slots_are_canonical_views():
    """align_rows=SUBLANE_PAD makes every pack8 slot slice exactly the leaf's
    own canonical 2D view — the precondition for per-slot kernel decode."""
    plan = bucketing.build_bucket_plan(ODD_SHAPES, "pack8")
    for b in plan.buckets:
        for s in b.slots:
            assert s.rows == kcommon.canonical_rows(s.size)


def test_plan_bucket_bytes_cap_and_oversized_leaf():
    fmt = "int8"
    row_bytes = bucketing.ROW_BYTES[fmt]
    cap = 4 * row_bytes  # 4 rows per bucket
    shapes = [jax.ShapeDtypeStruct((600,), jnp.float32),      # 2 rows
              jax.ShapeDtypeStruct((600,), jnp.float32),      # 2 rows
              jax.ShapeDtypeStruct((600,), jnp.float32),      # 2 rows -> split
              jax.ShapeDtypeStruct((5000,), jnp.float32)]     # 10 rows oversize
    plan = bucketing.build_bucket_plan(shapes, fmt, bucket_bytes=cap)
    assert [len(b.slots) for b in plan.buckets] == [2, 1, 1]
    # leaves are never split: the oversized leaf rides one bucket whole
    assert plan.buckets[-1].slots[0].rows == 10
    # unbounded: everything in one bucket
    one = bucketing.build_bucket_plan(shapes, fmt)
    assert len(one.buckets) == 1 and one.n_slots == 4


# ---------------------------------------------------------------------------
# payload round-trip: leaf -> rows -> bucket -> split is bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["int8", "f32"])
def test_assemble_split_roundtrip_bitwise(fmt):
    rng = np.random.RandomState(0)
    plan = bucketing.build_bucket_plan(ODD_SHAPES, fmt)
    dt = np.int8 if fmt == "int8" else np.float32
    leaves = [jnp.asarray(rng.randint(-100, 100, s.shape).astype(dt))
              for s in ODD_SHAPES]
    for b in plan.buckets:
        payloads = [bucketing.as_rows(leaves[s.index], fmt, s.rows)
                    for s in b.slots]
        buf = bucketing.assemble_bucket(payloads, b, fmt)
        assert buf.shape == (b.rows, bucketing.ROW_WIDTH[fmt])
        parts = bucketing.split_bucket(buf, b)
        for s, part in zip(b.slots, parts):
            assert part.shape == s.shape
            np.testing.assert_array_equal(np.asarray(part),
                                          np.asarray(leaves[s.index]))


def test_as_rows_preserves_flat_index():
    """Coordinate (r, c) of the row view must be flat index r*LANES + c —
    the counter-RNG layout invariant bucketing must not disturb."""
    n = 1000
    v = jnp.arange(n, dtype=jnp.float32)
    rows = bucketing.leaf_rows(n, 1)
    out = np.asarray(bucketing.as_rows(v, "f32", rows)).reshape(-1)
    np.testing.assert_array_equal(out[:n], np.arange(n, dtype=np.float32))
    assert (out[n:] == 0).all()


# ---------------------------------------------------------------------------
# the acceptance property: bucketed step == per-leaf step, bitwise
# ---------------------------------------------------------------------------

def _run(mode, **kw):

    step, state, batch, model, mesh, _ = drivers.build_mode_step(mode, **kw)
    with jax.sharding.set_mesh(mesh):
        out, metrics = step(state, batch)
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(out.params)]
    return leaves, metrics


@pytest.mark.parametrize("mode", list(drivers.MODE_SETUPS))
def test_bucketed_step_bitwise_equals_per_leaf(mode):
    ref, m_ref = _run(mode, bucketed=False)
    got, m_got = _run(mode, bucketed=True)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    # nnz attribution survives bucket granularity exactly
    assert float(m_ref["nnz_frac"]) == float(m_got["nnz_frac"])


def test_bucketed_per_slot_quorum_attribution():
    """Per-leaf quorum must address the right slot through the bucket: with a
    one-worker vote in {-1, 0, +1}, quorum=2 freezes exactly the leaves it is
    assigned to while quorum=1 leaves keep stepping."""
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step

    mode = "votes"
    _, server, vote_impl, _ = drivers.MODE_SETUPS[mode]
    comp = drivers.mode_comp(mode)
    model = drivers.tiny_model()
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    batch = drivers.tiny_batch(model.cfg.vocab_size)
    # freeze only the embed leaf
    quorum = {k: (2 if k == "embed" else 1) for k in model.param_shapes()}
    outs = []
    for bucketed in (False, True):
        scfg = TrainStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                               worker_axes=("data",), vote_impl=vote_impl,
                               quorum=quorum, donate=False,
                               backend="interpret", bucketed=bucketed)
        step = build_train_step(model, scfg, mesh)
        state = init_state(params, server=server, seed=7)
        with jax.sharding.set_mesh(mesh):
            out, _ = step(state, batch)
        outs.append(out.params)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # embed frozen (|vote| <= 1 < 2), at least one other leaf stepped
    assert np.array_equal(np.asarray(outs[1]["embed"]),
                          np.asarray(params["embed"]))
    moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves(outs[1]),
                                jax.tree_util.tree_leaves(params)))
    assert moved


# ---------------------------------------------------------------------------
# ledgers and launch-count budgets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(drivers.MODE_SETUPS))
def test_bucketed_census_pins_plan_ledger(mode):
    findings, census, payload, scalar = drivers.census_check(mode, bucketed=True)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert payload > 0
    assert census.payload_bytes({"data": drivers.HYPOTHETICAL_M}) == \
        pytest.approx(payload)


@pytest.mark.parametrize("bucketed", [False, True])
def test_count_budgets_exact(bucketed):
    findings, census, expected = drivers.count_check("votes", bucketed=bucketed)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert census.payload_count() == expected
    if bucketed:
        assert expected == 1  # whole tiny tree rides ONE collective


def test_count_ratio_floor_on_stacked_configs():
    findings, checks = drivers.count_ratio_checks()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert checks == len(drivers.RATIO_CONFIGS) * len(drivers.MODE_SETUPS)


def test_uplink_ledger_bucket_vs_plan_ledger():
    """plan_ledger must be exactly the per-bucket uplink_ledger_bucket sum
    (plus the one shared-linf vector term when requested)."""
    m = drivers.HYPOTHETICAL_M
    for mode in drivers.MODE_SETUPS:
        wire = drivers.mode_wire(mode, m)
        fmt = bucketing.wire_bucket_format(mode, wire)
        kw = {"rows_fn": wire.payload_rows} if fmt == "golomb" else {}
        plan = bucketing.build_bucket_plan(ODD_SHAPES, fmt,
                                           bucket_bytes=4096, **kw)
        pay, scal = bucketing.plan_ledger(mode, wire, plan)
        want_p = want_s = 0.0
        for b in plan.buckets:
            p, s = collectives.uplink_ledger_bucket(mode, wire, b.n_coords,
                                                    len(b.slots), rows=b.rows)
            want_p += p
            want_s += s
        assert pay == pytest.approx(want_p)
        assert scal == pytest.approx(want_s)
        pay_sh, _ = bucketing.plan_ledger(mode, wire, plan, share_linf=True)
        extra = collectives.allreduce_scalar_bytes(m) * plan.n_slots
        assert pay_sh == pytest.approx(pay + extra)
