"""`streamed`-mode distributed train step (DESIGN.md §3 mode 2) — for models
whose local gradient cannot exist in HBM all at once (qwen2-vl-72b, jamba-398b,
llama4-scout).

ALL parameters (block stacks AND embed/head) are FSDP-sharded along 'data'
(and over 'model' via GSPMD). One round:

  forward:  lax.scan over superblocks; each iteration all-gathers ONLY that
            block's param shards (bf16) and emits the block input — O(1 block)
            of gathered params live at any time.
  head:     gather embed/head, loss + vjp for the outer params.
  backward: reverse lax.scan; per superblock: re-gather params, recompute under
            jax.vjp (remat), compress the *local, unreduced* block gradient,
            exchange the wire-native message over the worker axes (any
            `vote_impl`: psum | hier | allgather_packed, and any wire mode:
            votes | scaled_votes | pack8 | decoded), then do ALL server math
            (sign / scaled-sign EF / scaled mean, SGD) on this rank's shard
            only — the full fp32 update tensor never exists. Gradients die
            block-by-block.

Counter streams are laid out identically to simple mode (leaf salt = canonical
tree position, counter = offset within the stacked leaf) — the cross-mode
equivalence test relies on this.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine, prng
from repro.core.algorithm import CompressionConfig
from repro.core.scopes import COUNTERS, FWD_BWD
from repro.dist import bucketing, collectives
from repro.dist.sharding import ACT_RULES_TRAIN
from repro.models.common import axis_rules, rms_norm
from repro.train import sampling
from repro.train.state import LrSchedule, TrainState

REPLICATED = -1  # sentinel: leaf not FSDP-sharded (None is not a pytree leaf)


@dataclasses.dataclass(frozen=True)
class StreamedStepConfig:
    compression: CompressionConfig
    lr: LrSchedule
    worker_axes: Sequence[str] = ("data",)
    fsdp_axis: str = "data"
    vote_impl: str = "psum"        # psum | hier | allgather_packed
    quorum: Any = 1                # server deadband: |votes| < quorum -> no step;
                                   # int (broadcast) or a pytree prefix of the
                                   # param tree with per-leaf ints
    donate: bool = True
    backend: Optional[str] = None  # kernel backend; None -> $REPRO_KERNEL_BACKEND
    bucketed: bool = False         # bucketized uplink + double-buffered
                                   # backward scan (exchange of superblock i
                                   # overlaps vjp/compress of superblock i-1)
    bucket_bytes: Optional[int] = None  # payload cap per bucket (None: one
                                        # bucket per superblock / outer group)
    golomb_p: Optional[float] = None    # plan-time nnz fraction sizing the
                                        # golomb wire's static capacity (None:
                                        # a target_sparsity budget's target)
    ring_chunk_rows: Optional[int] = None  # ring-pipelined gather: payload
                                           # rows per ppermute chunk (gather
                                           # wires only; None: monolithic
                                           # all_gather)
    participation: Optional[collectives.ParticipationSpec] = None
                                           # elastic participation: per-worker
                                           # vote weights + quorum-fraction
                                           # deadband + report dropout; None =
                                           # the legacy fixed-quorum path


# ---------------------------------------------------------------------------
# FSDP sharding layout
# ---------------------------------------------------------------------------

def fsdp_shard_axis(shape, n_shards: int, min_axis: int = 0, avoid=()) -> int:
    """Largest axis (>= min_axis, not in avoid) divisible by n_shards;
    REPLICATED if none. ``avoid`` holds axes already claimed by TP ('model')."""
    best, best_size = REPLICATED, 0
    for ax in range(min_axis, len(shape)):
        if ax in avoid:
            continue
        if shape[ax] % n_shards == 0 and shape[ax] >= n_shards and shape[ax] > best_size:
            best, best_size = ax, shape[ax]
    return best


def _spec_of(ax: int, axis_name: str) -> P:
    if ax == REPLICATED:
        return P()
    parts = [None] * (ax + 1)
    parts[ax] = axis_name
    return P(*parts)


def _is_logical(x):
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def build_fsdp_layout(shapes_tree, n_shards: int, axis_name: str, min_axis: int = 1,
                      logical_tree=None):
    """(PartitionSpec tree, shard-axis int tree). min_axis=1 skips the stacked R
    axis for block leaves; outer leaves use min_axis=0. When ``logical_tree`` is
    given, axes that TP would claim (DESIGN: vocab/heads/ff/expert -> model) are
    excluded so the data and model shardings never collide on one dim."""
    from repro.dist.sharding import TP_RULES

    leaves, treedef = jax.tree_util.tree_flatten(shapes_tree)
    if logical_tree is None:
        lg_leaves = [()] * len(leaves)
    else:
        lg_leaves = treedef.flatten_up_to(logical_tree)
    ax_leaves = []
    for s, lg in zip(leaves, lg_leaves):
        avoid = tuple(i for i, name in enumerate(lg)
                      if name is not None and TP_RULES.get(name) is not None)
        ax_leaves.append(fsdp_shard_axis(s.shape, n_shards, min_axis, avoid))
    axes_tree = jax.tree_util.tree_unflatten(treedef, ax_leaves)
    specs_tree = jax.tree_util.tree_map(lambda a: _spec_of(a, axis_name), axes_tree)
    return specs_tree, axes_tree


def streamed_shardings(model, mesh, fsdp_axis: str = "data"):
    """Single source of truth for streamed-mode parameter placement:
    returns (NamedSharding tree [FSDP+TP merged], shard-axis tree, shard-map
    PartitionSpec tree [manual/FSDP part only])."""
    from jax.sharding import NamedSharding
    from repro.dist.sharding import logical_to_spec, sanitize_spec

    shapes = model.param_shapes()
    logical = model.param_logical_axes()
    n = mesh.shape[fsdp_axis]
    named, manual_specs, axes = {}, {}, {}
    for k in shapes:
        min_axis = 1 if k == "blocks" else 0
        specs_k, axes_k = build_fsdp_layout(shapes[k], n, fsdp_axis,
                                            min_axis=min_axis, logical_tree=logical[k])

        lg_leaves, treedef = jax.tree_util.tree_flatten(logical[k], is_leaf=_is_logical)
        ax_leaves = treedef.flatten_up_to(axes_k)
        sh_leaves = treedef.flatten_up_to(shapes[k])
        merged = []
        for lg, ax, sds in zip(lg_leaves, ax_leaves, sh_leaves):
            # TP part first, sanitized to the actual dims (placement must divide)
            spec = list(sanitize_spec(logical_to_spec(lg), sds.shape, mesh))
            while len(spec) <= max(ax, 0):
                spec.append(None)
            if ax != REPLICATED:
                assert spec[ax] is None, (k, lg, ax)
                spec[ax] = fsdp_axis
            merged.append(NamedSharding(mesh, P(*spec)))
        named[k] = jax.tree_util.tree_unflatten(treedef, merged)
        manual_specs[k] = specs_k
        axes[k] = axes_k
    return named, axes, manual_specs


# ---------------------------------------------------------------------------
# Step builder
# ---------------------------------------------------------------------------

def build_streamed_train_step(model, step_cfg: StreamedStepConfig, mesh) -> Callable:
    cfg = model.cfg
    assert not cfg.tail_pattern, "streamed mode does not support tail blocks"
    assert not cfg.tie_embeddings, "streamed mode expects untied embeddings"
    comp = step_cfg.compression
    assert comp.local_steps == 1, "streamed mode implements Alg. 1 exchange (tau=1)"
    backend = engine.resolve_backend(step_cfg.backend)
    axes = tuple(step_cfg.worker_axes)
    # wire-mode negotiation (CompressorSpec lookup) resolved before tracing;
    # every mode — votes, scaled_votes, pack8, decoded — runs streamed
    mode = engine.wire_mode(comp, vote_impl=step_cfg.vote_impl)
    # built (and validated — hier demands two worker axes, sizes >= 1) at
    # step-build time, in the compressor's declared payload format; golomb
    # specs additionally resolve the plan-time nnz fraction that sizes the
    # entropy-coded wire's static capacity
    wire_fmt = engine.wire_payload_format(comp, mode,
                                          vote_impl=step_cfg.vote_impl)
    part = step_cfg.participation
    if part is not None:
        # elastic participation: loud build-time gates — the EF server cannot
        # be participation-normalized, and the weights must cover the mesh
        engine.check_participation_server(comp.server, comp.compressor)
    wire = collectives.make_vote_wire(
        step_cfg.vote_impl, axes, mesh, backend=backend,
        wire_format=wire_fmt,
        golomb_p=(engine.resolve_golomb_p(comp, step_cfg.golomb_p)
                  if wire_fmt == "golomb" else None),
        ring_chunk_rows=engine.resolve_ring_chunk_rows(
            step_cfg.ring_chunk_rows, step_cfg.vote_impl),
        participation=part)
    share_linf = engine.needs_shared_linf(comp)
    if mode != "votes" and engine.needs_server_ef(comp.server):
        raise ValueError(
            f"server {comp.server!r} keeps an error-feedback residual that "
            f"only updates on the integer vote wire, but compressor "
            f"{comp.compressor!r} rides the {mode!r} wire — the run would "
            f"silently aggregate by mean while carrying a dead full-model EF "
            f"residual; use a ternary vote-wire compressor or a plain 'mean' "
            f"server")
    fsdp_ax = step_cfg.fsdp_axis
    n_shards = mesh.shape[fsdp_ax]

    shapes = model.param_shapes()
    # per-leaf quorum, validated at build time; indexed by canonical leaf
    # position (same flat order as idx_tree below)
    quorum_flat = jax.tree_util.tree_leaves(
        engine.broadcast_quorum(step_cfg.quorum, shapes))
    # per-leaf quorum as a FRACTION of realized participation (build-time:
    # bad quorums and q_frac out of (0,1] fail before tracing)
    q_frac_flat = ([part.resolve_q_frac(q, wire.n_workers) for q in quorum_flat]
                   if part is not None else None)
    if mode != "votes" and any(q != 1 for q in quorum_flat):
        raise ValueError(
            f"quorum={step_cfg.quorum!r} is a vote-server deadband, but "
            f"compressor {comp.compressor!r} with server {comp.server!r} "
            f"rides the {mode!r} wire where it would be silently ignored; "
            f"use a vote server ({engine.VOTE_SERVERS}) or quorum=1")
    _, axes_all, manual_specs = streamed_shardings(model, mesh, fsdp_ax)
    block_specs, block_axes = manual_specs["blocks"], axes_all["blocks"]
    outer_keys = [k for k in shapes if k != "blocks"]
    outer_specs = {k: manual_specs[k] for k in outer_keys}
    outer_axes = {k: axes_all[k] for k in outer_keys}

    ax_flat = jax.tree_util.tree_leaves(block_axes)
    flat_shapes, shapes_treedef = jax.tree_util.tree_flatten(shapes)
    idx_tree = jax.tree_util.tree_unflatten(shapes_treedef, list(range(len(flat_shapes))))
    blocks_idx_flat = jax.tree_util.tree_leaves(idx_tree["blocks"])
    total_coords = sum(int(jnp.prod(jnp.array(s.shape))) for s in flat_shapes)
    # per-round per-device uplink ledger: block leaves exchange once per layer
    # at their per-layer size (padding is per-exchange, so it multiplies out),
    # outer leaves once at full size
    def exchange_bytes(n: int) -> float:
        # ONE ledger definition for both train modes (collectives.uplink_ledger)
        # — pinned against the traced collective census by repro.analysis
        return collectives.uplink_ledger(mode, wire, n, share_linf=share_linf)

    wire_ledger = sum(
        cfg.n_repeats * exchange_bytes(math.prod(s.shape[1:]))
        for s in jax.tree_util.tree_leaves(shapes["blocks"]))
    wire_ledger += sum(exchange_bytes(math.prod(s.shape))
                       for k in outer_keys
                       for s in jax.tree_util.tree_leaves(shapes[k]))
    # peak gather-payload residency (max over exchanges; 0.0 for psum wires
    # and the decoded-float path, which never materialize a gathered tensor)
    gather_hbm = 0.0
    if mode != "decoded":
        gather_hbm = max(
            [wire.gather_hbm_bytes(math.prod(s.shape[1:]))
             for s in jax.tree_util.tree_leaves(shapes["blocks"])]
            + [wire.gather_hbm_bytes(math.prod(s.shape))
               for k in outer_keys
               for s in jax.tree_util.tree_leaves(shapes[k])],
            default=0.0)

    # static bucket layouts (bucketed uplink): one plan for a superblock
    # layer's leaves (applied every scan iteration), one for the outer leaves
    block_plan = outer_plan = None
    blocks_treedef = jax.tree_util.tree_structure(shapes["blocks"])
    if step_cfg.bucketed:
        fmt = bucketing.wire_bucket_format(mode, wire)
        # golomb slots are CAPACITY rows — a pure (n, p) function owned by
        # the wire, not a coordinate-count row formula
        rows_fn = wire.payload_rows if fmt == "golomb" else None
        block_plan = bucketing.build_bucket_plan(
            [jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
             for s in jax.tree_util.tree_leaves(shapes["blocks"])],
            fmt, bucket_bytes=step_cfg.bucket_bytes, rows_fn=rows_fn)
        outer_plan = bucketing.build_bucket_plan(
            [shapes[k] for k in outer_keys], fmt,
            bucket_bytes=step_cfg.bucket_bytes, rows_fn=rows_fn)
        # the double-buffered scan primes with one zero bucket and drains the
        # last pending bucket after the scan -> n_repeats + 1 block-bucket
        # exchanges per step; the shared-linf vector pmax runs at compress
        # time, once per REAL layer (n_repeats)
        pay, scal = bucketing.streamed_plan_ledger(
            mode, wire, block_plan, outer_plan, cfg.n_repeats,
            share_linf=share_linf)
        wire_ledger = pay + scal
        gather_hbm = max(
            bucketing.plan_gather_hbm_bytes(mode, wire, block_plan),
            bucketing.plan_gather_hbm_bytes(mode, wire, outer_plan))

    def _gather(leaf, ax):
        return leaf if ax == REPLICATED else collectives.fsdp_all_gather(
            leaf, fsdp_ax, ax, tiled=True)

    def _slice(full, ax, shard_size):
        if ax == REPLICATED:
            return full
        start = jax.lax.axis_index(fsdp_ax) * shard_size
        return jax.lax.dynamic_slice_in_dim(full, start, shard_size, axis=ax)

    def leaf_update(p_shard, g_full, *, seed, counter_base, ef_shard, mask, lr,
                    shard_ax: int, leaf_size: int, quorum: int,
                    w_eff=None, q_frac=None):
        """compress(full) -> wire exchange(full) -> server math + SGD on the SHARD.

        The fp32 update/EF tensors only ever exist at shard size; the
        full-size artifacts are the bf16/f32 gradient (transient, from vjp)
        and the exchanged message (1 B/coord int8 votes for the psum wires,
        0.25 B/coord packed ternary or 1 B/coord pack8 levels for the gather
        wires, 4 B/coord fp32 for the decoded psum). Under elastic
        participation (``w_eff`` set) the exchange is the weighted one and
        the realized-participation total W replaces the fixed quorum /
        selected-count divisor; a per-coordinate W (psum wires) is sliced to
        the shard alongside the weighted vote."""
        shared = (collectives.worker_shared_linf(g_full, axes, mask=mask)
                  if share_linf else None)
        n_sel = collectives.scalar_psum(mask.astype(jnp.float32), axes)
        wtot = None
        if mode == "decoded":
            # per-worker decode scales / float payloads: decode locally, psum
            # fp32 — the wire object is bypassed, exactly like simple mode
            # (decoded_exchange is the one shared definition)
            msg = engine.compress_leaf(g_full, comp, seed, counter_base,
                                       backend=backend, shared_linf=shared)
            if part is not None:
                # the weight premultiplies the decode scale (w_eff == 1.0 is
                # a bitwise identity; a dropped worker decodes to exact
                # zeros) and the mean divisor becomes W
                agg, nnz = collectives.decoded_exchange(
                    msg.values, msg.scale * w_eff, mask, axes,
                    is_ternary=comp.is_ternary)
                wtot = collectives.scalar_psum(w_eff, axes)
            else:
                agg, nnz = collectives.decoded_exchange(
                    msg.values, msg.scale, mask, axes,
                    is_ternary=comp.is_ternary)
        else:
            msg = engine.compress_leaf(g_full, comp, seed, counter_base,
                                       backend=backend, wire=wire,
                                       shared_linf=shared)
            votes = wire.mask_message(msg.values, mask)
            nnz = wire.message_nnz(votes)
            if part is not None:
                agg, wtot = wire.exchange_weighted(
                    votes, g_full.size, g_full.shape, weight=w_eff,
                    scale=(msg.scale if mode == "pack8" else None))
            else:
                agg = wire.exchange(votes, g_full.size, g_full.shape,
                                    scale=(msg.scale if mode == "pack8" else None))
        shard_size = p_shard.shape[shard_ax] if shard_ax != REPLICATED else None
        vs = _slice(agg, shard_ax, shard_size)
        if part is not None:
            # W rides per-coordinate on the psum wires — slice it like the
            # weighted vote; gather wires return one scalar
            wt = wtot if jnp.ndim(wtot) == 0 else _slice(wtot, shard_ax,
                                                         shard_size)
            if mode == "votes":
                new_shard, new_ef = engine.server_apply(
                    p_shard, vs, comp, lr=lr, ef=ef_shard,
                    part_total=wt, q_frac=q_frac, backend=backend)
            else:
                new_shard, new_ef = engine.server_apply(
                    p_shard, vs, comp, lr=lr, ef=ef_shard, n_sel=wt,
                    server="mean",
                    scale=(msg.scale if mode == "scaled_votes" else None),
                    backend=backend)
        elif mode == "votes":
            # shards partition the leaf, so the scaled-sign L1 reduces across them
            l1_reduce = ((lambda part: collectives.scalar_psum(part, fsdp_ax))
                         if shard_ax != REPLICATED else None)
            new_shard, new_ef = engine.server_apply(
                p_shard, vs, comp, lr=lr, ef=ef_shard, n_sel=n_sel,
                leaf_size=leaf_size, l1_reduce=l1_reduce, quorum=quorum,
                backend=backend)
        else:
            # mean-server wires: scaled_votes carries the ONE shared decode
            # scale outside the sum; pack8/decoded sums arrive pre-dequantized
            new_shard, new_ef = engine.server_apply(
                p_shard, vs, comp, lr=lr, ef=ef_shard, n_sel=n_sel,
                server="mean",
                scale=(msg.scale if mode == "scaled_votes" else None),
                backend=backend)
        return new_shard, new_ef, nnz

    # ------------------------------------------------------------------
    # bucketed uplink: group-level compress / exchange+apply
    # ------------------------------------------------------------------
    # static per-leaf metadata in group order (blocks: per-layer flat leaves,
    # outer: outer_keys order) — quorum/shard-axis lookups resolved at build
    block_shard_axes = [a - 1 if a != REPLICATED else REPLICATED for a in ax_flat]
    block_quorums = [quorum_flat[i] for i in blocks_idx_flat]
    outer_shard_axes = [axes_all[k] for k in outer_keys]
    outer_quorums = [quorum_flat[idx_tree[k]] for k in outer_keys]
    block_q_fracs = ([q_frac_flat[i] for i in blocks_idx_flat]
                     if part is not None else None)
    outer_q_fracs = ([q_frac_flat[idx_tree[k]] for k in outer_keys]
                     if part is not None else None)

    def _group_compress(plan_, g_leaves, seeds, bases, mask, w_eff=None):
        """Per-leaf compress into bucket slices (seeds/counter_base unchanged
        vs the per-leaf path — slot payloads are bitwise the per-leaf wire
        messages), assembled into the plan's wire buffers. Returns
        (bufs, svecs, nnz): one payload buffer and one (n_slots,) f32
        decode-scale vector per bucket (1.0 where the mode carries none).
        Under elastic participation the decoded mode's decode scale is
        premultiplied by ``w_eff`` (w_eff == 1.0 is a bitwise identity)."""
        slots = {s.index: s for b in plan_.buckets for s in b.slots}
        shared_vec = (collectives.worker_shared_linf_many(g_leaves, axes, mask=mask)
                      if share_linf else None)
        payloads = [None] * len(g_leaves)
        scales = [jnp.float32(1.0)] * len(g_leaves)
        nnz = jnp.float32(0.0)
        for j, g in enumerate(g_leaves):
            shared = shared_vec[j] if share_linf else None
            if mode == "decoded":
                msg = engine.compress_leaf(g, comp, seeds[j], bases[j],
                                           backend=backend, shared_linf=shared)
                sc = msg.scale * w_eff if part is not None else msg.scale
                dec, z = collectives.decoded_message(
                    msg.values, sc, mask, is_ternary=comp.is_ternary)
                payloads[j] = bucketing.as_rows(dec, plan_.fmt, slots[j].rows)
                nnz += z
            else:
                msg = engine.compress_leaf_rows(
                    g, comp, seeds[j], bases[j], rows=slots[j].rows,
                    backend=backend, wire=wire, shared_linf=shared)
                payloads[j] = wire.mask_message(msg.values, mask)
                nnz += wire.message_nnz(payloads[j])
                scales[j] = msg.scale
        bufs = tuple(bucketing.assemble_bucket(
            [payloads[s.index] for s in b.slots], b, plan_.fmt)
            for b in plan_.buckets)
        svecs = tuple(jnp.stack([scales[s.index] for s in b.slots])
                      for b in plan_.buckets)
        return bufs, svecs, nnz

    def _group_apply(plan_, bufs, svecs, ps_leaves, ef_leaves, shard_axes,
                     quorums, *, n_sel, lr, w_eff=None, w_psum=None,
                     q_fracs=None):
        """ONE exchange per bucket, then the per-leaf server math + SGD on
        this rank's shards — identical server semantics (per-leaf quorum, EF
        residuals, shared-scale decode, l1_reduce) at bucket granularity.
        Under elastic participation (``w_eff`` set) the exchange is the
        weighted one: W is per-slot per-coordinate on the psum wires (sliced
        to the shard like the vote) and one scalar on the gather wires; the
        decoded mode's W is the caller's precomputed ``w_psum``."""
        new_ps = [None] * len(ps_leaves)
        new_efs = [None] * len(ps_leaves)
        for b, buf, sv in zip(plan_.buckets, bufs, svecs):
            wtots = None
            if mode == "decoded":
                parts = bucketing.split_bucket(
                    collectives.decoded_exchange_bucket(buf, axes), b)
                wtots = w_psum
            elif part is not None:
                if mode == "pack8":
                    parts, wtots = wire.exchange_bucket_weighted(
                        buf, b, weight=w_eff, scale=sv)
                else:
                    parts, wtots = wire.exchange_bucket_weighted(
                        buf, b, weight=w_eff)
            elif mode == "pack8":
                parts = wire.exchange_bucket(buf, b, scale=sv)
            else:
                parts = wire.exchange_bucket(buf, b)
            for pos, (s, agg) in enumerate(zip(b.slots, parts)):
                j = s.index
                sh_ax = shard_axes[j]
                shard_size = (ps_leaves[j].shape[sh_ax]
                              if sh_ax != REPLICATED else None)
                vs = _slice(agg, sh_ax, shard_size)
                if part is not None:
                    wt = (wtots[pos] if isinstance(wtots, (list, tuple))
                          else wtots)
                    wt = wt if jnp.ndim(wt) == 0 else _slice(wt, sh_ax,
                                                             shard_size)
                    if mode == "votes":
                        new_ps[j], new_efs[j] = engine.server_apply(
                            ps_leaves[j], vs, comp, lr=lr, ef=ef_leaves[j],
                            part_total=wt, q_frac=q_fracs[j],
                            backend=backend)
                    else:
                        new_ps[j], new_efs[j] = engine.server_apply(
                            ps_leaves[j], vs, comp, lr=lr, ef=ef_leaves[j],
                            n_sel=wt, server="mean",
                            scale=(sv[pos] if mode == "scaled_votes" else None),
                            backend=backend)
                elif mode == "votes":
                    l1_reduce = ((lambda part: collectives.scalar_psum(part, fsdp_ax))
                                 if sh_ax != REPLICATED else None)
                    new_ps[j], new_efs[j] = engine.server_apply(
                        ps_leaves[j], vs, comp, lr=lr, ef=ef_leaves[j],
                        n_sel=n_sel, leaf_size=s.size, l1_reduce=l1_reduce,
                        quorum=quorums[j], backend=backend)
                else:
                    new_ps[j], new_efs[j] = engine.server_apply(
                        ps_leaves[j], vs, comp, lr=lr, ef=ef_leaves[j],
                        n_sel=n_sel, server="mean",
                        scale=(sv[pos] if mode == "scaled_votes" else None),
                        backend=backend)
        return new_ps, new_efs

    def body(state: TrainState, batch):
        with axis_rules(ACT_RULES_TRAIN, mesh):
            return _body_inner(state, batch)

    def _body_inner(state: TrainState, batch):
        params = state.params
        widx = collectives.worker_index(axes)
        n_workers = collectives.worker_count(axes)
        rseed = sampling.round_seed(state.seed, state.step)
        wseed = prng.fold_seed(rseed, 0x5EED) + widx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        mask = sampling.participation_mask(rseed, state.step, widx, comp.worker_sample_fraction)
        w_eff = w_psum = None
        if part is not None:
            # elastic: the round's effective reporting set is the sampled set
            # minus chaos dropouts; w_eff = static weight x report bit is the
            # weight that rides the wire (exact 0.0 for a silent worker)
            mask = mask & sampling.report_mask(rseed, state.step, widx,
                                               part.dropout)
            w_eff = (part.weight_of(widx, n_workers)
                     * mask.astype(jnp.float32))
            w_psum = collectives.scalar_psum(w_eff, axes)
        lr = step_cfg.lr(state.step)
        positions = batch["positions"]
        positions3 = batch.get("positions3")
        has_ef = state.ef_residual is not None

        def gather_block(block_slice):
            leaves, treedef = jax.tree_util.tree_flatten(block_slice)
            out = [_gather(l, (a - 1 if a != REPLICATED else a))
                   for l, a in zip(leaves, ax_flat)]
            return jax.tree_util.tree_unflatten(treedef, out)

        # ---------------- forward ----------------
        def fwd_body(h, block_shard):
            full = gather_block(block_shard)
            return model.superblock_apply(full, h, positions, positions3), h

        # ---------------- head / loss ----------------
        def head_fn(outer_p, h):
            hn = rms_norm(h, outer_p["final_norm"], cfg.norm_eps)
            return model.head_loss(outer_p, hn, batch["labels"])

        with jax.named_scope(FWD_BWD):
            outer_full = {k: _gather(params[k], outer_axes[k]) for k in outer_keys}
            h0 = model.embed_stage(outer_full if cfg.input_kind == "tokens" else params,
                                   batch)
            h_final, h_inputs = jax.lax.scan(fwd_body, h0, params["blocks"])
            loss, head_vjp = jax.vjp(head_fn, outer_full, h_final)
            g_outer, g_h = head_vjp(jnp.float32(1.0))

        # ---------------- backward over superblocks ----------------
        if block_plan is not None:
            # bucketed + double-buffered: iteration for superblock l first
            # applies the PENDING buckets (superblock l+1's compressed
            # gradient, carried from the previous iteration), then runs this
            # block's vjp + compress. The pending exchange has no data
            # dependency on the vjp, so the collective flies while the
            # recompute/compress math runs. A zero bucket primes the pipe
            # (first iteration, results dropped) and the last pending bucket
            # drains after the scan -> n_repeats + 1 exchanges per bucket.
            n_sel_b = collectives.scalar_psum(mask.astype(jnp.float32), axes)
            seeds_b = [prng.fold_seed(wseed, i) for i in blocks_idx_flat]
            block_leaves = jax.tree_util.tree_leaves(params["blocks"])
            ps0 = tuple(jnp.zeros(l.shape[1:], l.dtype) for l in block_leaves)
            if has_ef:
                ef0 = tuple(jnp.zeros(l.shape[1:], l.dtype)
                            for l in jax.tree_util.tree_leaves(state.ef_residual["blocks"]))
            else:
                ef0 = tuple(jnp.float32(0.0) for _ in block_leaves)
            bufs0 = tuple(jnp.zeros((b.rows, bucketing.ROW_WIDTH[block_plan.fmt]),
                                    bucketing.ROW_DTYPE[block_plan.fmt])
                          for b in block_plan.buckets)
            svecs0 = tuple(jnp.ones((len(b.slots),), jnp.float32)
                           for b in block_plan.buckets)

            def bwd_body_b(carry, xs):
                g_h, nnz_acc, pbufs, psvecs, pps, pefs = carry
                if has_ef:
                    block_shard, h_in, layer, ef_slice = xs
                else:
                    block_shard, h_in, layer = xs
                # drain the pending (upper) superblock FIRST — its exchange
                # overlaps this block's recompute below
                new_shards, new_efs = _group_apply(
                    block_plan, pbufs, psvecs, list(pps), list(pefs),
                    block_shard_axes, block_quorums, n_sel=n_sel_b, lr=lr,
                    w_eff=w_eff, w_psum=w_psum, q_fracs=block_q_fracs)

                def fwd(bp, h):
                    return model.superblock_apply(bp, h, positions, positions3)

                with jax.named_scope(FWD_BWD):
                    full = gather_block(block_shard)
                    _, vjp = jax.vjp(fwd, full, h_in)
                    g_block, g_h_prev = vjp(g_h)
                g_leaves, g_def = jax.tree_util.tree_flatten(g_block)
                ps_leaves = g_def.flatten_up_to(block_shard)
                ef_leaves = (g_def.flatten_up_to(ef_slice) if has_ef
                             else [jnp.float32(0.0)] * len(g_leaves))
                bases = [layer.astype(jnp.uint32) * jnp.uint32(g.size)
                         for g in g_leaves]
                bufs, svecs, nnz = _group_compress(
                    block_plan, g_leaves, seeds_b, bases, mask, w_eff=w_eff)
                outs = (jax.tree_util.tree_unflatten(g_def, new_shards),)
                if has_ef:
                    outs = outs + (jax.tree_util.tree_unflatten(g_def, new_efs),)
                carry = (g_h_prev, nnz_acc + nnz, bufs, svecs,
                         tuple(ps_leaves), tuple(ef_leaves))
                return carry, outs

            xs = (params["blocks"], h_inputs, jnp.arange(cfg.n_repeats))
            if has_ef:
                xs = xs + (state.ef_residual["blocks"],)
            carry0 = (g_h, jnp.float32(0.0), bufs0, svecs0, ps0, ef0)
            (g_h0, nnz_acc, pbufs, psvecs, pps, pefs), ys = jax.lax.scan(
                bwd_body_b, carry0, xs, reverse=True)
            # drain: the final pending buckets hold superblock 0's update.
            # ys[l] holds superblock l+1's (iteration l applied the PENDING
            # layer); ys[n_repeats-1] is the priming dummy — dropped.
            fin_shards, fin_efs = _group_apply(
                block_plan, pbufs, psvecs, list(pps), list(pefs),
                block_shard_axes, block_quorums, n_sel=n_sel_b, lr=lr,
                w_eff=w_eff, w_psum=w_psum, q_fracs=block_q_fracs)

            def _shift(stacked, first):
                return jnp.concatenate([first[None], stacked[:-1]], axis=0)

            new_blocks = jax.tree_util.tree_map(
                _shift, ys[0],
                jax.tree_util.tree_unflatten(blocks_treedef, fin_shards))
            new_ef_blocks = (jax.tree_util.tree_map(
                _shift, ys[1],
                jax.tree_util.tree_unflatten(blocks_treedef, fin_efs))
                if has_ef else None)

            # ---- embed backward + bucketed outer group ----
            g_embed = None
            if cfg.input_kind == "tokens":
                def embed_fn(emb):
                    return model.embed_stage({"embed": emb}, batch)
                with jax.named_scope(FWD_BWD):
                    _, embed_vjp = jax.vjp(embed_fn, outer_full["embed"])
                    (g_embed,) = embed_vjp(g_h0)

            g_outer_leaves = []
            for k in outer_keys:
                g_k = g_outer[k]
                if k == "embed" and g_embed is not None:
                    g_k = g_k + g_embed
                g_outer_leaves.append(g_k)
            seeds_o = [prng.fold_seed(wseed, idx_tree[k]) for k in outer_keys]
            bases_o = [jnp.uint32(0)] * len(outer_keys)
            o_bufs, o_svecs, o_nnz = _group_compress(
                outer_plan, g_outer_leaves, seeds_o, bases_o, mask,
                w_eff=w_eff)
            nnz_acc = nnz_acc + o_nnz
            o_efs = ([state.ef_residual[k] for k in outer_keys] if has_ef
                     else [jnp.float32(0.0)] * len(outer_keys))
            o_new, o_new_efs = _group_apply(
                outer_plan, o_bufs, o_svecs, [params[k] for k in outer_keys],
                o_efs, outer_shard_axes, outer_quorums, n_sel=n_sel_b, lr=lr,
                w_eff=w_eff, w_psum=w_psum, q_fracs=outer_q_fracs)

            new_params = {"blocks": new_blocks}
            new_ef = {"blocks": new_ef_blocks} if has_ef else None
            for k, np_, ne in zip(outer_keys, o_new, o_new_efs):
                new_params[k] = np_
                if has_ef:
                    new_ef[k] = ne

            with jax.named_scope(COUNTERS):
                loss_mean = collectives.scalar_psum(loss, axes) / n_workers
                nnz_mean = (collectives.scalar_psum(nnz_acc, axes) / n_workers
                            / jnp.float32(total_coords))
            metrics = {"loss": loss_mean, "lr": lr, "nnz_frac": nnz_mean,
                       "participated": n_sel_b,
                       "wire_bytes_per_device": jnp.float32(wire_ledger),
                       "gather_hbm_bytes": jnp.float32(gather_hbm)}
            new_state = TrainState(params=new_params, ef_residual=new_ef,
                                   step=state.step + 1, seed=state.seed)
            return new_state, metrics

        def bwd_body(carry, xs):
            g_h, nnz_acc = carry
            if has_ef:
                block_shard, h_in, layer, ef_slice = xs
            else:
                block_shard, h_in, layer = xs

            def fwd(bp, h):
                return model.superblock_apply(bp, h, positions, positions3)

            with jax.named_scope(FWD_BWD):
                full = gather_block(block_shard)
                _, vjp = jax.vjp(fwd, full, h_in)
                g_block, g_h_prev = vjp(g_h)

            g_leaves, g_def = jax.tree_util.tree_flatten(g_block)
            ps_leaves = g_def.flatten_up_to(block_shard)
            ef_leaves = (g_def.flatten_up_to(ef_slice) if has_ef
                         else [jnp.float32(0.0)] * len(g_leaves))

            new_shards, new_efs = [], []
            for g, p_shard, ef, ax, leaf_idx in zip(
                    g_leaves, ps_leaves, ef_leaves, ax_flat, blocks_idx_flat):
                seed_i = prng.fold_seed(wseed, leaf_idx)
                base = layer.astype(jnp.uint32) * jnp.uint32(g.size)
                sh_ax = ax - 1 if ax != REPLICATED else REPLICATED
                new_shard, new_ef, nnz = leaf_update(
                    p_shard, g, seed=seed_i, counter_base=base, ef_shard=ef,
                    mask=mask, lr=lr, shard_ax=sh_ax, leaf_size=g.size,
                    quorum=quorum_flat[leaf_idx], w_eff=w_eff,
                    q_frac=(q_frac_flat[leaf_idx] if part is not None
                            else None))
                nnz_acc = nnz_acc + nnz
                new_shards.append(new_shard)
                new_efs.append(new_ef)
            outs = (jax.tree_util.tree_unflatten(g_def, new_shards),)
            if has_ef:
                outs = outs + (jax.tree_util.tree_unflatten(g_def, new_efs),)
            return (g_h_prev, nnz_acc), outs

        xs = (params["blocks"], h_inputs, jnp.arange(cfg.n_repeats))
        if has_ef:
            xs = xs + (state.ef_residual["blocks"],)
        (g_h0, nnz_acc), ys = jax.lax.scan(bwd_body, (g_h, jnp.float32(0.0)), xs, reverse=True)
        new_blocks = ys[0]
        new_ef_blocks = ys[1] if has_ef else None

        # ---------------- embed backward + outer updates ----------------
        g_embed = None
        if cfg.input_kind == "tokens":
            def embed_fn(emb):
                return model.embed_stage({"embed": emb}, batch)
            with jax.named_scope(FWD_BWD):
                _, embed_vjp = jax.vjp(embed_fn, outer_full["embed"])
                (g_embed,) = embed_vjp(g_h0)

        new_params = {"blocks": new_blocks}
        new_ef = {"blocks": new_ef_blocks} if has_ef else None
        for k in outer_keys:
            g_k = g_outer[k]
            if k == "embed" and g_embed is not None:
                g_k = g_k + g_embed
            seed_i = prng.fold_seed(wseed, idx_tree[k])
            ef_k = state.ef_residual[k] if has_ef else jnp.float32(0.0)
            new_shard, new_ef_k, nnz = leaf_update(
                params[k], g_k, seed=seed_i, counter_base=jnp.uint32(0),
                ef_shard=ef_k, mask=mask, lr=lr,
                shard_ax=outer_axes[k], leaf_size=g_k.size,
                quorum=quorum_flat[idx_tree[k]], w_eff=w_eff,
                q_frac=(q_frac_flat[idx_tree[k]] if part is not None
                        else None))
            nnz_acc = nnz_acc + nnz
            new_params[k] = new_shard
            if has_ef:
                new_ef[k] = new_ef_k

        with jax.named_scope(COUNTERS):
            loss_mean = collectives.scalar_psum(loss, axes) / n_workers
            nnz_mean = (collectives.scalar_psum(nnz_acc, axes) / n_workers
                        / jnp.float32(total_coords))
            metrics = {"loss": loss_mean, "lr": lr, "nnz_frac": nnz_mean,
                       "participated": collectives.scalar_psum(
                           mask.astype(jnp.float32), axes),
                       "wire_bytes_per_device": jnp.float32(wire_ledger),
                       "gather_hbm_bytes": jnp.float32(gather_hbm)}
        new_state = TrainState(params=new_params, ef_residual=new_ef,
                               step=state.step + 1, seed=state.seed)
        return new_state, metrics

    # ------------------------------------------------------------------
    # shard_map wiring
    # ------------------------------------------------------------------
    p_specs = {"blocks": block_specs}
    for k in outer_keys:
        p_specs[k] = outer_specs[k]
    state_specs = TrainState(
        params=p_specs,
        ef_residual=(p_specs if engine.needs_server_ef(comp.server) else None),
        step=P(), seed=P())
    batch_spec = P(axes if len(axes) > 1 else axes[0])

    wrapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, P()),
        axis_names=engine.manual_axes(backend, mesh, set(axes) | {fsdp_ax}),
        check_vma=False,
    )

    def train_step(state, batch):
        return wrapped(state, batch)

    if step_cfg.donate:
        return jax.jit(train_step, donate_argnums=(0,))
    return jax.jit(train_step)


def fsdp_param_shardings(model, mesh, fsdp_axis: str = "data"):
    """NamedShardings (FSDP over data + TP over model) to place params for the
    streamed trainer."""
    named, _, _ = streamed_shardings(model, mesh, fsdp_axis)
    return named
