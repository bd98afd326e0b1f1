"""Wire-byte accounting for one training round: the paper's communication claim
on TPU terms. First-principles per-device bytes for every exchange variant, per
architecture — the numbers the collective roofline term is built from, and the
before/after ledger for §Perf.

Exchange granularity is per TRAINER MODE: the simple trainer exchanges each
(stacked) leaf once at full size, but the streamed trainer exchanges every
block leaf once PER SUPERBLOCK at its per-layer size — n_repeats exchanges,
each paying its own canonical-view padding. The ledger columns bill the real
granularity (``exchange_sizes``); billing a streamed stack as one exchange
understates the padding tax by up to n_repeats x.

Two packed-wire columns: ``packed_model`` is the closed-form d/4-per-worker
model; ``packed_real`` is the *actual* ledger from the VoteWire implementation
(``collectives.PackedVoteWire.wire_bytes`` summed over the real per-exchange
sizes), which ships padded canonical views — the delta is the padding tax the
idealized model hides. ``bucketed_real`` is the bucketized-uplink twin
(``repro.dist.bucketing`` plans): one collective per bucket, padding amortized
per bucket, launch counts collapsed (the ``launch_ratio`` column).

Ring columns (``mono_peak_hbm`` / ``ring_peak_hbm`` / ``ring_launches``) cost
the ring-pipelined gather at the production chunk size: peak gathered-payload
residency of the monolithic all_gather (M x payload) vs the chunked ppermute
ring (send + recv chunk, O(1) in M), plus the ring's launch count (one
(M-1)-hop ring per chunk). A third traced census (``ring_census_bytes``)
asserts the ring program bills the SAME fabric bytes as the monolithic ledger.

Elastic-participation columns (``elastic_real`` / ``weight_side`` /
``weight_tax``): the weighted vote's packed gather ships the same payload plus
one (1,) f32 participation weight per peer per exchange — weight_side =
launches x (M-1) x 4 B, asserted to be EXACTLY the elastic-vs-legacy ledger
delta. The step section adds ``elastic_full`` (weighted exchange, full
participation) and ``elastic_mask50`` (50% per-round report dropout — masked
payloads are exact zeros but every byte still rides the fixed-shape wire).

The step section runs one real train step per configuration (per-leaf vs
bucketed wire, both trainers, plus ``ring_*`` chunked-ppermute configs) on
forced host devices and records the step's own counts (wire bytes, gather HBM
bytes, participation); nothing is timed. It writes the tracked
``BENCH_collectives.json`` at the repo root (``--quick`` writes
``BENCH_collectives.quick.json`` — the CI smoke artifact — so it can't
clobber the baseline).

  python -m benchmarks.bench_collectives            # full table + step counts
  python -m benchmarks.bench_collectives --quick    # CI smoke
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time
from collections import Counter
from pathlib import Path

# before any jax backend init: the step section wants real host devices
# (harmless if another module initialized jax first — the section falls back)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from benchmarks.common import csv_header, csv_row
from repro.configs.registry import ARCH_IDS, get_config, trainer_mode

ROOT = Path(__file__).resolve().parents[1]
OUT_PATH = ROOT / "BENCH_collectives.json"            # tracked baseline
QUICK_OUT_PATH = ROOT / "BENCH_collectives.quick.json"  # CI smoke; never tracked


# ---------------------------------------------------------------------------
# per-trainer-mode exchange granularity
# ---------------------------------------------------------------------------

def exchange_sizes(cfg, trainer: str) -> Counter:
    """{exchange_coords: launches_per_round} at the trainer's REAL uplink
    granularity. simple: one exchange per stacked leaf. streamed: one exchange
    per block leaf PER SUPERBLOCK (n_repeats launches at per-layer size — the
    scan re-exchanges each layer slice), outer leaves once."""
    import jax

    from repro.models.model import Model

    shapes = Model(cfg).param_shapes()
    sizes: Counter = Counter()
    if trainer == "simple":
        for s in jax.tree_util.tree_leaves(shapes):
            sizes[int(math.prod(s.shape))] += 1
        return sizes
    for s in jax.tree_util.tree_leaves(shapes["blocks"]):
        sizes[int(math.prod(s.shape[1:]))] += cfg.n_repeats
    for k in shapes:
        if k == "blocks":
            continue
        for s in jax.tree_util.tree_leaves(shapes[k]):
            sizes[int(math.prod(s.shape))] += 1
    return sizes


def packed_real_bytes(cfg, trainer: str, n_data: int = 16, n_pod: int = 1) -> float:
    """Per-device bytes of the real allgather_packed wire for one round:
    (M-1) x padded 2-bit payload, summed over the trainer's real exchanges."""
    from repro.dist.collectives import PackedVoteWire

    wire = PackedVoteWire(axes=("data",), n_workers=n_data * n_pod)
    return sum(count * wire.wire_bytes(n)
               for n, count in exchange_sizes(cfg, trainer).items())


def elastic_packed_bytes(cfg, trainer: str, n_data: int = 16,
                         n_pod: int = 1) -> tuple[float, float]:
    """(elastic_total, weight_side) per-device bytes of the elastic packed
    wire for one round: the payload is unchanged, but every exchange also
    gathers each peer's (1,) f32 participation weight — the side channel the
    weighted vote normalizes by. weight_side = launches x (M-1) x 4 B."""
    from repro.dist.collectives import ParticipationSpec, PackedVoteWire

    wire = PackedVoteWire(axes=("data",), n_workers=n_data * n_pod,
                          participation=ParticipationSpec(q_frac=0.5))
    total = weight = 0.0
    for n, count in exchange_sizes(cfg, trainer).items():
        total += count * (wire.wire_bytes(n)
                          + wire.weight_bytes() * wire.ring_chunks(n))
        weight += count * wire.weight_bytes() * wire.ring_chunks(n)
    return total, weight


def packed_census_bytes(cfg, trainer: str, n_data: int = 16, n_pod: int = 1) -> float:
    """Traced-jaxpr cross-check of the ``packed_real`` ledger column: run the
    repro.analysis CollectiveCensus over the actual PackedVoteWire exchange
    program (one trace per distinct exchange size), ring-costed at the same M.
    Equals packed_real_bytes unless the wire implementation and the ledger
    drift apart — which is exactly what the column is for."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import collective_census
    from repro.launch.mesh import make_mesh
    from repro.dist.collectives import PackedVoteWire
    from repro.kernels import common as kcommon
    from repro.launch.mesh import make_host_mesh

    m = n_data * n_pod
    wire = PackedVoteWire(axes=("data",), n_workers=m, backend="interpret")
    mesh = make_host_mesh(1, 1)
    P = jax.sharding.PartitionSpec
    total = 0.0
    for n, count in exchange_sizes(cfg, trainer).items():
        packed = jax.ShapeDtypeStruct(
            (kcommon.canonical_rows(n), kcommon.LANES // 4), jnp.uint8)
        fn = jax.shard_map(lambda p, n=n: wire.exchange(p, n, (n,)),
                           mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        census = collective_census(jax.make_jaxpr(fn)(packed))
        total += census.total_bytes({"data": m}) * count
    return total


# ---------------------------------------------------------------------------
# bucketized uplink: bytes + launch counts
# ---------------------------------------------------------------------------

def _bucket_plans(cfg, trainer: str, wire):
    """(plans, launches) — the BucketPlans one bucketed round applies and the
    payload-launch count they cost (streamed block plans ride n_repeats + 1
    times: the double-buffered scan's prime/drain)."""
    import jax

    from repro.dist import bucketing
    from repro.models.model import Model

    fmt = wire.native_format
    shapes = Model(cfg).param_shapes()
    if trainer == "simple":
        plan = bucketing.build_bucket_plan(
            jax.tree_util.tree_leaves(shapes), fmt)
        return {"plan": plan}, len(plan.buckets)
    block_plan = bucketing.build_bucket_plan(
        [jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
         for s in jax.tree_util.tree_leaves(shapes["blocks"])], fmt)
    outer_plan = bucketing.build_bucket_plan(
        [s for k in shapes if k != "blocks"
         for s in jax.tree_util.tree_leaves(shapes[k])], fmt)
    launches = ((cfg.n_repeats + 1) * len(block_plan.buckets)
                + len(outer_plan.buckets))
    return {"block": block_plan, "outer": outer_plan}, launches


def bucketed_real_bytes(cfg, trainer: str, n_data: int = 16,
                        n_pod: int = 1) -> float:
    """Per-device bytes of the bucketized packed wire for one round — the
    ``bucketing.plan_ledger`` twin of ``packed_real_bytes``."""
    from repro.dist import bucketing
    from repro.dist.collectives import PackedVoteWire

    wire = PackedVoteWire(axes=("data",), n_workers=n_data * n_pod)
    plans, _ = _bucket_plans(cfg, trainer, wire)
    if trainer == "simple":
        pay, scal = bucketing.plan_ledger("votes", wire, plans["plan"])
        return pay + scal
    pay, scal = bucketing.streamed_plan_ledger(
        "votes", wire, plans["block"], plans["outer"], cfg.n_repeats)
    return pay + scal


def bucketed_census_bytes(cfg, trainer: str, n_data: int = 16,
                          n_pod: int = 1) -> float:
    """Traced cross-check of ``bucketed_real_bytes``: census the actual
    ``exchange_bucket`` program per distinct bucket, ring-costed at M."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import collective_census
    from repro.dist import bucketing
    from repro.launch.mesh import make_mesh
    from repro.dist.collectives import PackedVoteWire
    from repro.launch.mesh import make_host_mesh

    m = n_data * n_pod
    wire = PackedVoteWire(axes=("data",), n_workers=m, backend="interpret")
    mesh = make_host_mesh(1, 1)
    P = jax.sharding.PartitionSpec
    plans, _ = _bucket_plans(cfg, trainer, wire)
    if trainer == "simple":
        reps = [(plans["plan"], 1)]
    else:
        reps = [(plans["block"], cfg.n_repeats + 1), (plans["outer"], 1)]
    total = 0.0
    for plan, trips in reps:
        for b in plan.buckets:
            buf = jax.ShapeDtypeStruct(
                (b.rows, bucketing.ROW_WIDTH[plan.fmt]),
                bucketing.ROW_DTYPE[plan.fmt])
            fn = jax.shard_map(
                lambda p, b=b: wire.exchange_bucket(p, b),
                mesh=mesh, in_specs=P(), out_specs=[P()] * len(b.slots),
                check_vma=False)
            census = collective_census(jax.make_jaxpr(fn)(buf))
            total += census.total_bytes({"data": m}) * trips
    return total


def launch_counts(cfg, trainer: str, n_data: int = 16, n_pod: int = 1):
    """(per_leaf_launches, bucketed_launches) payload collectives per round."""
    from repro.dist.collectives import PackedVoteWire

    per_leaf = sum(exchange_sizes(cfg, trainer).values())
    wire = PackedVoteWire(axes=("data",), n_workers=n_data * n_pod)
    _, bucketed = _bucket_plans(cfg, trainer, wire)
    return per_leaf, bucketed


# ---------------------------------------------------------------------------
# ring-pipelined gather: peak payload residency + hop counts
# ---------------------------------------------------------------------------

def ring_stats(cfg, trainer: str, n_data: int = 16, n_pod: int = 1) -> dict:
    """Ring-gather columns at the documented production chunk size
    (``collectives.DEFAULT_RING_CHUNK_ROWS``): peak gathered-payload HBM of
    the monolithic all_gather (M x the largest exchange payload) vs the ring
    (send + recv chunk only), and the ring's payload launch count — one
    (M-1)-hop ppermute ring per chunk, where the monolithic wire launches one
    all_gather per exchange."""
    from repro.dist.collectives import DEFAULT_RING_CHUNK_ROWS, PackedVoteWire

    m = n_data * n_pod
    mono = PackedVoteWire(axes=("data",), n_workers=m)
    ring = PackedVoteWire(axes=("data",), n_workers=m,
                          ring_chunk_rows=DEFAULT_RING_CHUNK_ROWS)
    sizes = exchange_sizes(cfg, trainer)
    mono_hbm = max(mono.gather_hbm_bytes(n) for n in sizes)
    ring_hbm = max(ring.gather_hbm_bytes(n) for n in sizes)
    launches = sum(count * ring.ring_chunks(n) for n, count in sizes.items())
    return {"mono_peak_hbm": mono_hbm, "ring_peak_hbm": ring_hbm,
            "hbm_ratio": mono_hbm / ring_hbm,
            "ring_launches": launches, "ring_hops": launches * (m - 1)}


def ring_census_bytes(cfg, trainer: str, n_data: int = 16,
                      n_pod: int = 1) -> float:
    """Traced cross-check of the RING wire against the SAME ``packed_real``
    ledger: census the chunked-ppermute exchange program per distinct
    exchange size. The ring moves exactly the bytes the monolithic gather
    moves — (M-1) x payload, chunk by chunk — it just never holds them all,
    so this must equal ``packed_real_bytes`` to the byte. The chunk size is
    picked per exchange to give a genuinely multi-chunk (~3 chunk) program
    while keeping the trace small; byte-invariance holds for any chunk size."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import collective_census
    from repro.launch.mesh import make_mesh
    from repro.dist.collectives import PackedVoteWire
    from repro.kernels import common as kcommon
    from repro.launch.mesh import make_host_mesh

    m = n_data * n_pod
    mesh = make_host_mesh(1, 1)
    P = jax.sharding.PartitionSpec
    total = 0.0
    for n, count in exchange_sizes(cfg, trainer).items():
        rows = kcommon.canonical_rows(n)
        chunk = max(32, math.ceil(rows / 3 / 32) * 32)
        wire = PackedVoteWire(axes=("data",), n_workers=m,
                              backend="interpret", ring_chunk_rows=chunk)
        packed = jax.ShapeDtypeStruct((rows, kcommon.LANES // 4), jnp.uint8)
        fn = jax.shard_map(lambda p, n=n, w=wire: w.exchange(p, n, (n,)),
                           mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        census = collective_census(jax.make_jaxpr(fn)(packed))
        total += census.total_bytes({"data": m}) * count
    return total


# ---------------------------------------------------------------------------
# closed-form byte models
# ---------------------------------------------------------------------------

def wire_model(n_params: int, mode: str, n_data: int = 16, n_pod: int = 1,
               variant: str = "sparsign_int8") -> dict:
    """Per-device wire bytes for one round's gradient exchange (+FSDP traffic).

    ring all-reduce:    2*(M-1)/M * payload
    ring all-gather:    (M-1)/M * payload
    """
    m = n_data * n_pod
    ar = lambda b: 2 * (m - 1) / m * b
    ag_data = lambda b: (n_data - 1) / n_data * b
    grad_exchange = {
        "fp32_dp": ar(4 * n_params),                   # uncompressed baseline
        "bf16_dp": ar(2 * n_params),
        "sparsign_int8": ar(1 * n_params),             # ternary votes, int8 wire
        "sparsign_int8_hier": 2 * (n_data - 1) / n_data * n_params
                               + (2 * (n_pod - 1) / max(n_pod, 1)) * 2 * n_params,
        "sparsign_packed_allgather": (m - 1) * (n_params / 4.0),  # 2-bit, no reduce
    }[variant]
    fsdp = ag_data(2 * n_params) if mode == "streamed" else 0.0  # bf16 param gather
    return {"grad_exchange": grad_exchange, "fsdp_gather": fsdp,
            "total": grad_exchange + fsdp}


# ---------------------------------------------------------------------------
# step-level wire counts: per-leaf vs bucketed, both trainers
# ---------------------------------------------------------------------------

def _simple_step_counts(modes, records):
    import jax

    from repro.analysis import drivers
    from repro.launch.mesh import make_mesh

    for mode in modes:
        for bucketed in (False, True):
            step, state, batch, model, mesh, _ = drivers.build_mode_step(
                mode, bucketed=bucketed)
            with jax.sharding.set_mesh(mesh):
                _, metrics = step(state, batch)
            records.append({
                "case": f"step_simple/{mode}/{'bucketed' if bucketed else 'per_leaf'}",
                "trainer": "simple", "wire_mode": mode, "bucketed": bucketed,
                "wire_bytes_per_device": float(metrics["wire_bytes_per_device"]),
                "gather_hbm_bytes": float(metrics["gather_hbm_bytes"]),
            })
            csv_row([records[-1]["case"],
                     f"{records[-1]['wire_bytes_per_device']:.0f}",
                     f"{records[-1]['gather_hbm_bytes']:.0f}"])


def _elastic_step_counts(records):
    """Elastic-participation rows on the votes wire: the weighted
    exchange at full participation, and the chaos configuration (50%%
    per-round report dropout) where half the fleet's payloads are masked to
    exact zeros but — SPMD ships fixed shapes — every byte still rides."""
    import jax

    from repro.analysis import drivers
    from repro.launch.mesh import make_mesh
    from repro.dist.collectives import ParticipationSpec

    for tag, part in (
            ("elastic_full", drivers.participation_spec()),
            ("elastic_mask50", ParticipationSpec(q_frac=0.5, dropout=0.5))):
        step, state, batch, model, mesh, _ = drivers.build_mode_step(
            "votes", participation=part)
        with jax.sharding.set_mesh(mesh):
            _, metrics = step(state, batch)
        records.append({
            "case": f"step_simple/votes/{tag}",
            "trainer": "simple", "wire_mode": "votes", "bucketed": False,
            "wire_bytes_per_device": float(metrics["wire_bytes_per_device"]),
            "gather_hbm_bytes": float(metrics["gather_hbm_bytes"]),
            "participated": float(metrics["participated"]),
        })
        csv_row([records[-1]["case"],
                 f"{records[-1]['wire_bytes_per_device']:.0f}",
                 f"{records[-1]['gather_hbm_bytes']:.0f}"])


def _streamed_step_counts(modes, records):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import drivers
    from repro.core.algorithm import CompressionConfig
    from repro.core.budgets import BudgetConfig
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    from repro.train.state import LrSchedule, init_state
    from repro.train.step_streamed import (StreamedStepConfig,
                                           build_streamed_train_step,
                                           fsdp_param_shardings)

    n_dev = jax.device_count()
    if n_dev < 2:
        print("# streamed steps skipped: need >= 2 devices "
              f"(have {n_dev})")
        return
    data = 4 if n_dev >= 8 else 2
    mesh = make_mesh((data, n_dev // data), ("data", "model"))
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shardings = fsdp_param_shardings(model, mesh, "data")
    params = jax.tree_util.tree_map(jax.device_put, params, shardings)
    rng = np.random.RandomState(0)
    b, s = 8, 16
    batch = {
        "inputs": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }
    lr = LrSchedule(base=0.01)
    for mode in modes:
        comp_name, server, vote_impl, value = drivers._setup_of(mode)
        kind = "target_sparsity" if mode.endswith("golomb") else "fixed"
        comp = CompressionConfig(compressor=comp_name,
                                 budget=BudgetConfig(kind=kind, value=value),
                                 server=server)
        ring_rows = (drivers.RING_SWEEP_CHUNK_ROWS
                     if mode in drivers.RING_SETUPS else None)
        for bucketed in (False, True):
            step = build_streamed_train_step(model, StreamedStepConfig(
                compression=comp, lr=lr, worker_axes=("data",),
                fsdp_axis="data", vote_impl=vote_impl, donate=False,
                backend="jnp", bucketed=bucketed,
                ring_chunk_rows=ring_rows), mesh)
            state = init_state(params, server=server, seed=42)
            with jax.sharding.set_mesh(mesh):
                _, metrics = step(state, batch)
            records.append({
                "case": f"step_streamed/{mode}/"
                        f"{'double_buffered' if bucketed else 'per_leaf'}",
                "trainer": "streamed", "wire_mode": mode, "bucketed": bucketed,
                "wire_bytes_per_device": float(metrics["wire_bytes_per_device"]),
                "gather_hbm_bytes": float(metrics["gather_hbm_bytes"]),
            })
            csv_row([records[-1]["case"],
                     f"{records[-1]['wire_bytes_per_device']:.0f}",
                     f"{records[-1]['gather_hbm_bytes']:.0f}"])


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(fast: bool = False, out: Path | None = None):
    import jax

    print("# per-device wire bytes per round, by exchange variant (single pod, 16 data)")
    csv_header(["arch", "mode", "params_B", "fp32_dp", "sparsign_int8",
                "vs_fp32", "fsdp_gather", "hier_2pod", "packed_model",
                "packed_real", "packed_census", "pad_tax", "bucketed_real",
                "bucket_pad_tax", "launches", "launches_bucketed",
                "launch_ratio", "mono_peak_hbm", "ring_peak_hbm",
                "hbm_ratio", "ring_launches", "elastic_real",
                "weight_side", "weight_tax"])
    table = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        n = cfg.param_count()
        mode = trainer_mode(arch)
        base = wire_model(n, mode, variant="fp32_dp")
        ours = wire_model(n, mode, variant="sparsign_int8")
        hier = wire_model(n, mode, n_pod=2, variant="sparsign_int8_hier")
        packed = wire_model(n, mode, variant="sparsign_packed_allgather")
        real = packed_real_bytes(cfg, mode)
        census = packed_census_bytes(cfg, mode)
        assert census == real, (
            f"{arch}: traced census {census:.6g} != ledger {real:.6g}")
        breal = bucketed_real_bytes(cfg, mode)
        bcensus = bucketed_census_bytes(cfg, mode)
        assert bcensus == breal, (
            f"{arch}: bucketed census {bcensus:.6g} != ledger {breal:.6g}")
        # the ring wire moves the SAME bytes over the fabric — assert its
        # traced census against the monolithic ledger, to the byte
        rcensus = ring_census_bytes(cfg, mode)
        assert rcensus == real, (
            f"{arch}: ring census {rcensus:.6g} != ledger {real:.6g}")
        per_leaf, bucketed = launch_counts(cfg, mode)
        ratio = per_leaf / max(bucketed, 1)
        rs = ring_stats(cfg, mode)
        ereal, wside = elastic_packed_bytes(cfg, mode)
        assert ereal == real + wside, (
            f"{arch}: elastic packed wire must be payload + weight side "
            f"channel exactly, got {ereal:.6g} vs {real + wside:.6g}")
        csv_row([arch, mode, f"{n/1e9:.2f}e9",
                 f"{base['grad_exchange']:.3e}", f"{ours['grad_exchange']:.3e}",
                 f"{base['grad_exchange']/ours['grad_exchange']:.1f}x",
                 f"{ours['fsdp_gather']:.3e}", f"{hier['grad_exchange']:.3e}",
                 f"{packed['grad_exchange']:.3e}", f"{real:.3e}",
                 f"{census:.3e}",
                 f"{real / packed['grad_exchange'] - 1:+.1%}",
                 f"{breal:.3e}",
                 f"{breal / packed['grad_exchange'] - 1:+.1%}",
                 per_leaf, bucketed, f"{ratio:.1f}x",
                 f"{rs['mono_peak_hbm']:.3e}", f"{rs['ring_peak_hbm']:.3e}",
                 f"{rs['hbm_ratio']:.1f}x", rs["ring_launches"],
                 f"{ereal:.3e}", f"{wside:.3e}",
                 f"{wside / real:+.2%}"])
        table.append({
            "arch": arch, "trainer": mode, "params": n,
            "packed_real_bytes": real, "bucketed_real_bytes": breal,
            "launches_per_leaf": per_leaf, "launches_bucketed": bucketed,
            "launch_ratio": ratio,
            "mono_peak_hbm_bytes": rs["mono_peak_hbm"],
            "ring_peak_hbm_bytes": rs["ring_peak_hbm"],
            "gather_hbm_ratio": rs["hbm_ratio"],
            "ring_launches": rs["ring_launches"],
            "ring_hops": rs["ring_hops"],
            "elastic_real_bytes": ereal,
            "weight_side_bytes": wside,
        })

    print("\n# step counts: per-leaf vs bucketed wire "
          f"(jax backend={jax.default_backend()}, {jax.device_count()} devices)")
    csv_header(["case", "wire_bytes_per_device", "gather_hbm_bytes"])
    modes = (("votes", "ring_pack2") if fast
             else ("votes", "scaled_votes", "pack8", "decoded",
                   "ring_pack2", "ring_pack8"))
    records: list[dict] = []
    _simple_step_counts(modes, records)
    _elastic_step_counts(records)
    _streamed_step_counts(modes, records)

    doc = {
        "schema": 1,
        "bench": "collectives",
        "jax_backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
        "quick": fast,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": ("ledger table bills the trainer's REAL exchange granularity "
                 "(streamed: n_repeats per-layer exchanges per block leaf); "
                 "step rows give the per-leaf and the bucketed (simple) / "
                 "double-buffered (streamed) wire's own counts from one host "
                 "step; nothing is timed. Ring columns "
                 "are at collectives.DEFAULT_RING_CHUNK_ROWS: the ring moves "
                 "the same fabric bytes as the monolithic gather (asserted "
                 "via the traced ring census) but holds only ~2 chunks of "
                 "payload instead of M exchanges' worth; ring_* step "
                 "rows run the chunked ppermute wire and report its "
                 "gather_hbm_bytes metric. elastic_real/weight_side columns "
                 "bill the weighted exchange's (M-1)x4B-per-launch f32 weight "
                 "side channel; elastic_* step rows run the weighted vote at "
                 "full participation and under 50% report dropout."),
        "ledger": table,
        "results": records,
    }
    out = out or (QUICK_OUT_PATH if fast else OUT_PATH)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"# wrote {out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke subset")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args()
    main(fast=args.quick, out=args.out)
