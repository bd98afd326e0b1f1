"""The ring-pipelined payload gather (``ring_chunk_rows``): chunk framing,
build-time validation, the gather-HBM/ledger math, and the decode-equivalence
pins that hold without a multi-device mesh (chunked decode == whole decode on
gathered arrays; the M=1 degenerate ring bitwise-equals the monolithic wire
and the psum oracle end-to-end). The multi-worker ring-vs-monolithic sweep
(8 devices, both train modes, both backends) runs in tests/mdev/check_wires.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.dist import bucketing, collectives
from repro.kernels import common
from repro.kernels.pack2bit.ops import pack2bit_op
from repro.kernels.pack8.ops import qsgd8_pack8_op

OTHER = "interpret" if jax.default_backend() != "tpu" else "pallas"


# ---------------------------------------------------------------------------
# chunk framing (static plan-time helpers)
# ---------------------------------------------------------------------------

def test_ring_perm_cycle():
    assert collectives.ring_perm(4) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    # M=1 degenerates to the (trace-legal) identity; the hop loop never runs
    assert collectives.ring_perm(1) == [(0, 0)]


def test_ring_chunk_spans():
    spans = collectives._ring_chunk_spans
    assert spans(96, None) == ((0, 96),)            # monolithic: one chunk
    assert spans(96, 96) == ((0, 96),)
    assert spans(96, 32) == ((0, 32), (32, 32), (64, 32))
    assert spans(70, 32) == ((0, 32), (32, 32), (64, 6))   # short tail
    assert spans(8, 32) == ((0, 8),)                # payload smaller than chunk
    # spans tile the payload exactly, in row order
    for total, chunk in [(97, 32), (1, 32), (320, 64)]:
        s = spans(total, chunk)
        assert s[0][0] == 0 and sum(nr for _, nr in s) == total
        for (a, na), (b, _) in zip(s, s[1:]):
            assert a + na == b


def _pack8_plan(sizes, bucket_bytes=None):
    return bucketing.build_bucket_plan(
        [jax.ShapeDtypeStruct((n,), jnp.float32) for n in sizes],
        "pack8", bucket_bytes=bucket_bytes)


def test_slot_groups_and_chunk_segments():
    plan = _pack8_plan([1000, 513, 4096, 70000])
    (b,) = plan.buckets
    # groups partition the slots in order, each group under the cap unless a
    # single slot alone exceeds it (then it rides the ring as one oversized
    # chunk)
    for cap in (32, 64, 128):
        groups = collectives._slot_groups(b.slots, cap)
        flat = [s for g in groups for s in g]
        assert flat == list(b.slots)
        for g in groups:
            rows = sum(s.rows for s in g)
            assert rows <= cap or len(g) == 1
    assert collectives._slot_groups(b.slots, None) == (tuple(b.slots),)
    # chunk/slot intersection segments: cover each chunk's slot rows exactly
    for r0, nr in collectives._ring_chunk_spans(b.rows, 32):
        segs = collectives._chunk_segments(b.slots, r0, nr)
        covered = sum(seg_rows for _, _, _, seg_rows in segs)
        in_slots = sum(max(0, min(r0 + nr, s.row_start + s.rows)
                           - max(r0, s.row_start)) for s in b.slots)
        assert covered == in_slots
        for i, s, a, seg_rows in segs:
            assert b.slots[i] is s
            assert s.row_start <= a and a + seg_rows <= s.row_start + s.rows


# ---------------------------------------------------------------------------
# build-time validation
# ---------------------------------------------------------------------------

def test_make_vote_wire_ring_validation():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    # a ring request on a fabric-reduction wire is a loud contradiction
    with pytest.raises(ValueError, match="gather-wire concept"):
        collectives.make_vote_wire("psum", ("data",), mesh, ring_chunk_rows=32)
    with pytest.raises(ValueError, match="gather-wire concept"):
        collectives.make_vote_wire("hier", ("data", "model"), mesh,
                                   ring_chunk_rows=32)
    # chunk size must keep every chunk a valid kernel grid
    for bad in (31, 0, -32, 33):
        with pytest.raises(ValueError, match="sublane"):
            collectives.make_vote_wire("allgather_packed", ("data",), mesh,
                                       ring_chunk_rows=bad)
    wire = collectives.make_vote_wire("allgather_packed", ("data",), mesh,
                                      ring_chunk_rows=64)
    assert isinstance(wire, collectives.PackedVoteWire)
    assert wire.ring_chunk_rows == 64
    for fmt, cls in (("pack8", collectives.Pack8Wire),
                     ("golomb", collectives.GolombWire)):
        w = collectives.make_vote_wire(
            "allgather_packed", ("data",), mesh, wire_format=fmt,
            golomb_p=(0.05 if fmt == "golomb" else None), ring_chunk_rows=64)
        assert isinstance(w, cls) and w.ring_chunk_rows == 64


def test_resolve_ring_chunk_rows():
    assert engine.resolve_ring_chunk_rows(None, "psum") is None
    assert engine.resolve_ring_chunk_rows(None, "allgather_packed") is None
    assert engine.resolve_ring_chunk_rows(256, "allgather_packed") == 256
    with pytest.raises(ValueError, match="allgather_packed"):
        engine.resolve_ring_chunk_rows(256, "psum")
    with pytest.raises(ValueError, match="sublane"):
        engine.resolve_ring_chunk_rows(48, "allgather_packed")


# ---------------------------------------------------------------------------
# ledger math: ring chunks, gather-HBM residency, uplink bytes
# ---------------------------------------------------------------------------

def test_pack2_ring_ledger_math():
    m = 16
    n = 96 * common.LANES                 # exactly 96 canonical rows
    mono = collectives.PackedVoteWire(axes=("data",), n_workers=m)
    ring = collectives.PackedVoteWire(axes=("data",), n_workers=m,
                                      ring_chunk_rows=32)
    row_b = common.LANES // 4
    assert mono.ring_chunks(n) == 1 and ring.ring_chunks(n) == 3
    assert mono.gather_hbm_bytes(n) == m * 96 * row_b
    assert ring.gather_hbm_bytes(n) == 2 * 32 * row_b
    # total fabric bytes are ring-invariant: every chunk visits every worker
    assert mono.wire_bytes(n) == ring.wire_bytes(n)
    assert (collectives.uplink_ledger("votes", mono, n)
            == collectives.uplink_ledger("votes", ring, n))


def test_pack8_ring_ledger_math():
    m = 16
    n = 96 * common.LANES
    mono = collectives.Pack8Wire(axes=("data",), n_workers=m)
    ring = collectives.Pack8Wire(axes=("data",), n_workers=m,
                                 ring_chunk_rows=32)
    assert ring.ring_chunks(n) == 3
    assert mono.gather_hbm_bytes(n) == m * 96 * common.LANES
    assert ring.gather_hbm_bytes(n) == 2 * 32 * common.LANES
    # the chunked ring re-ships the decode scale once per chunk
    assert (collectives.uplink_ledger("pack8", ring, n)
            == mono.wire_bytes(n) + 3 * mono.scalar_bytes())
    assert (collectives.uplink_ledger("pack8", mono, n)
            == mono.wire_bytes(n) + mono.scalar_bytes())
    # bucketed variant: the (n_slots,) scale vector re-ships per chunk too
    pay_m, sc_m = collectives.uplink_ledger_bucket("pack8", mono, n, 4)
    pay_r, sc_r = collectives.uplink_ledger_bucket("pack8", ring, n, 4,
                                                   ring_chunks=3)
    assert pay_r - pay_m == 2 * (m - 1) * 4 * 4 and sc_m == sc_r == 0.0


def test_golomb_ring_ledger_math():
    from repro.kernels.golomb.ref import ROW_BYTES, golomb_rows
    m = 16
    n = 1 << 20
    mono = collectives.GolombWire(axes=("data",), n_workers=m, p=0.05)
    ring = collectives.GolombWire(axes=("data",), n_workers=m, p=0.05,
                                  ring_chunk_rows=256)
    rows = golomb_rows(n, 0.05)
    # a per-leaf coded stream is one self-describing chunk regardless of size
    assert ring.ring_chunks(n) == 1
    assert mono.gather_hbm_bytes(n) == m * rows * ROW_BYTES
    assert ring.gather_hbm_bytes(n) == 2 * rows * ROW_BYTES
    assert mono.gather_hbm_bytes(n) == (m / 2) * ring.gather_hbm_bytes(n)
    assert mono.wire_bytes(n) == ring.wire_bytes(n)


def test_psum_wires_have_no_gather_hbm():
    for w in (collectives.VoteWire(axes=("data",), n_workers=16),
              collectives.HierVoteWire(axes=("pod", "data"), n_workers=16,
                                       inner_size=8, outer_size=2)):
        assert w.gather_hbm_bytes(1 << 20) == 0.0
        assert w.ring_chunks(1 << 20) == 1


def test_plan_gather_hbm_bytes():
    plan = _pack8_plan([1000, 513, 4096, 70000])
    mono = collectives.Pack8Wire(axes=("data",), n_workers=16)
    ring = collectives.Pack8Wire(axes=("data",), n_workers=16,
                                 ring_chunk_rows=32)
    got_m = bucketing.plan_gather_hbm_bytes("pack8", mono, plan)
    got_r = bucketing.plan_gather_hbm_bytes("pack8", ring, plan)
    assert got_m == max(mono.bucket_gather_hbm_bytes(b) for b in plan.buckets)
    assert got_r == max(ring.bucket_gather_hbm_bytes(b) for b in plan.buckets)
    assert got_r < got_m
    # the decoded-float path bypasses the wire: no gathered tensor, ever
    assert bucketing.plan_gather_hbm_bytes("decoded", mono, plan) == 0.0


# ---------------------------------------------------------------------------
# chunked decode == whole decode (gathered arrays, no mesh)
# ---------------------------------------------------------------------------

def test_pack2_chunked_decode_matches_whole():
    """The framing invariant the pack2 ring rides on: canonical rows decode
    independently, so decoding a gathered payload span-by-span (per worker,
    summed in any order — int32 adds commute) equals the whole-payload fused
    decode at every coordinate."""
    m, n = 4, 40000                      # 79 rows -> padded to 96 -> 3 chunks
    rng = np.random.RandomState(0)
    payloads = [pack2bit_op(jnp.asarray(rng.randint(-1, 2, n), jnp.int8))
                for _ in range(m)]
    gathered = jnp.stack(payloads)
    rows = gathered.shape[1]
    whole = np.asarray(collectives._packed_decode_sum(
        gathered, rows * common.LANES, (rows * common.LANES,), backend=None))
    parts = []
    for r0, nr in collectives._ring_chunk_spans(rows, 32):
        acc = np.zeros(nr * common.LANES, np.int32)
        for w in range(m):   # reversed worker order: ring arrival at rank 0
            chunk = gathered[m - 1 - w, r0:r0 + nr][None]
            acc += np.asarray(collectives._packed_decode_sum(
                chunk, nr * common.LANES, (nr * common.LANES,), backend=None))
        parts.append(acc)
    assert np.array_equal(np.concatenate(parts), whole)
    assert np.array_equal(
        whole[:n], sum(np.asarray(collectives._packed_decode_sum(
            p[None], n, (n,), backend=None), np.int32) for p in payloads))


# ---------------------------------------------------------------------------
# M=1 degenerate ring: bitwise the monolithic wire, under shard_map
# ---------------------------------------------------------------------------

def _m1_exchange(wire, payload, n, scale=None):
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)

    def f(p):
        return wire.exchange(p, n, (n,), scale=scale)

    # jitted, as the trainers run it: jax 0.9's eager partial-manual
    # shard_map with check_vma=False rejects its own P() out_specs
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                              axis_names={"data"}, check_vma=False))
    with jax.sharding.set_mesh(mesh):
        return np.asarray(g(payload))


@pytest.mark.parametrize("n", [40000, 7 * 1237])   # multi-chunk + odd shapes
def test_pack2_ring_exchange_m1_bitwise(n):
    rng = np.random.RandomState(1)
    t = jnp.asarray(rng.randint(-1, 2, n), jnp.int8)
    payload = pack2bit_op(t)
    kw = dict(axes=("data",), n_workers=1)
    mono = _m1_exchange(collectives.PackedVoteWire(**kw), payload, n)
    ring = _m1_exchange(collectives.PackedVoteWire(ring_chunk_rows=32, **kw),
                        payload, n)
    assert np.array_equal(ring, mono)
    assert np.array_equal(ring, np.asarray(t, np.int32))


@pytest.mark.parametrize("n", [40000, 7 * 1237])
def test_pack8_ring_exchange_m1_bitwise(n):
    """At M=1 there are no cross-worker adds to re-associate, so even the f32
    pack8 ring is bitwise the monolithic decode (each coordinate lives in
    exactly one chunk; the per-chunk kernel rounds it identically)."""
    rng = np.random.RandomState(2)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    from repro.core.compressors import qsgd8_scale
    sc = qsgd8_scale(g)
    payload = qsgd8_pack8_op(g, sc, 3)
    kw = dict(axes=("data",), n_workers=1, backend=OTHER)
    mono = _m1_exchange(collectives.Pack8Wire(**kw), payload, n,
                        scale=jnp.float32(sc))
    ring = _m1_exchange(collectives.Pack8Wire(ring_chunk_rows=32, **kw),
                        payload, n, scale=jnp.float32(sc))
    assert np.array_equal(ring, mono)


def test_golomb_ring_exchange_m1_bitwise():
    n = 40000
    rng = np.random.RandomState(3)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    comp = CompressionConfig(
        compressor="sparsign_golomb",
        budget=BudgetConfig(kind="target_sparsity", value=0.05),
        server="majority_vote")
    kw = dict(axes=("data",), n_workers=1, p=0.05, backend=OTHER)
    mono_w = collectives.GolombWire(**kw)
    msg = engine.compress_leaf(g, comp, 9, backend=OTHER, wire=mono_w)
    mono = _m1_exchange(mono_w, msg.values, n)
    ring = _m1_exchange(collectives.GolombWire(ring_chunk_rows=256, **kw),
                        msg.values, n)
    assert np.array_equal(ring, mono)


# ---------------------------------------------------------------------------
# M=1 degenerate ring, end-to-end: the ring step == the psum oracle stream
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.models.model import Model
    cfg = ModelConfig(name="ring-tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      pattern=(LayerSpec(mixer="attn"),), dtype="float32",
                      attn_chunk=8, q_chunk=8, loss_chunk=8, remat=False)
    return Model(cfg)


def _one_step(model, params, batch, mesh, comp, **cfg_kw):
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_simple import TrainStepConfig, build_train_step
    scfg = TrainStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                           worker_axes=("data",), donate=False, **cfg_kw)
    step = build_train_step(model, scfg, mesh)
    state = init_state(params, server=comp.server, seed=7)
    with jax.sharding.set_mesh(mesh):
        out, metrics = step(state, batch)
    return jax.tree_util.tree_map(np.asarray, out.params), metrics


@pytest.mark.parametrize("bucketed", [False, True])
def test_ring_step_m1_matches_psum_oracle(bucketed):
    from repro.launch.mesh import make_host_mesh
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(4)
    batch = {
        "inputs": jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(8), (2, 8)).astype(jnp.int32),
    }
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    ref, _ = _one_step(model, params, batch, mesh, comp, vote_impl="psum")
    for backend in ("jnp", OTHER):
        got, m = _one_step(model, params, batch, mesh, comp,
                           vote_impl="allgather_packed", backend=backend,
                           bucketed=bucketed, ring_chunk_rows=32)
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(ref)[0],
                jax.tree_util.tree_flatten_with_path(got)[0]):
            assert np.array_equal(a, b), (backend, jax.tree_util.keystr(ka))
        # the residency metric is emitted from the ring wire's own model
        wire = collectives.PackedVoteWire(axes=("data",), n_workers=1,
                                          ring_chunk_rows=32)
        if bucketed:
            plan = bucketing.build_bucket_plan(
                [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in
                 jax.tree_util.tree_leaves(model.param_shapes())], "pack2")
            want = bucketing.plan_gather_hbm_bytes("votes", wire, plan)
        else:
            want = max(wire.gather_hbm_bytes(s.size) for s in
                       jax.tree_util.tree_leaves(model.param_shapes()))
        assert float(m["gather_hbm_bytes"]) == want


def test_ring_step_config_validation_is_loud():
    from repro.launch.mesh import make_host_mesh
    from repro.train.state import LrSchedule
    from repro.train.step_simple import TrainStepConfig, build_train_step
    from repro.train.step_streamed import StreamedStepConfig
    model = _tiny_model()
    mesh = make_host_mesh(1, 1)
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    with pytest.raises(ValueError, match="allgather_packed"):
        build_train_step(model, TrainStepConfig(
            compression=comp, lr=LrSchedule(base=0.05), worker_axes=("data",),
            vote_impl="psum", ring_chunk_rows=32), mesh)
    # the streamed config carries the same knob
    cfg = StreamedStepConfig(compression=comp, lr=LrSchedule(base=0.05),
                             vote_impl="allgather_packed", ring_chunk_rows=64)
    assert cfg.ring_chunk_rows == 64
