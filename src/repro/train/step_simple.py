"""`simple`-mode distributed train step (DESIGN.md §3 mode 1).

Top level: ``jax.shard_map`` manual over the worker axes ('pod','data') — the
paper's M workers — and auto (GSPMD) over 'model' (TP/EP/SP). Parameters are
replicated across workers and sharded over 'model' by their placement +
``hint()`` constraints inside the model code.

Per round (Algorithm 1 / Algorithm 2 with tau=1..):
  1. every worker computes the local gradient of its microbatch
     (optionally tau compressed local steps, Alg. 2),
  2. compresses each gradient leaf with its worker-specific counter stream,
     in the vote wire's native format (int8 ternary for the psum wires, fused
     2-bit packed for `allgather_packed`),
  3. one wire exchange over the worker axes = upload + server sum
     (`repro.dist.collectives.VoteWire`: psum | hier | allgather_packed),
  4. C(.) (majority vote sign, scaled-sign with server-side EF, or the scaled
     mean for shared-scale ternary baselines) computed redundantly everywhere
     = free downlink; over the monolithic 2-bit gather the majority vote
     decodes the gathered payloads inside its update kernel
     (``engine.server_apply_packed``), and no vote sum reaches HBM,
  5. SGD update; params stay bitwise identical across workers.

Which wire a compressor rides is negotiated from the CompressorSpec table
(``engine.wire_mode``): ternary compressors with a worker-invariant scale
(scale-free, or TernGrad's psum-max'd shared_max) exchange ternary votes on
the integer/packed wire even under a mean server; qsgd8's int8 sign*level
payload rides the 1 B/coord pack8 gather (+ per-worker f32 scales) when
``vote_impl='allgather_packed'``; per-worker-scale ternary baselines
(qsgd_1bit/scaled_sign under mean) and the float formats psum decoded
float32 — honestly costing fp32 collective bytes, which is exactly the
communication gap the paper's tables report.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine, prng
from repro.core.algorithm import CompressionConfig
from repro.core.scopes import COUNTERS, FWD_BWD, scoped
from repro.dist import bucketing, collectives
from repro.dist.sharding import ACT_RULES_TRAIN
from repro.models.common import axis_rules
from repro.train import sampling
from repro.train.state import LrSchedule, TrainState


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    compression: CompressionConfig
    lr: LrSchedule
    local_lr: float = 1.0          # eta_L (Alg. 2)
    worker_axes: Sequence[str] = ("data",)
    vote_impl: str = "psum"        # psum | hier | allgather_packed
    quorum: Any = 1                # server deadband: |votes| < quorum -> no step;
                                   # int (broadcast) or a pytree prefix of the
                                   # param tree with per-leaf ints
    donate: bool = True
    backend: Optional[str] = None  # kernel backend; None -> $REPRO_KERNEL_BACKEND
    bucketed: bool = False         # bucketized uplink: one collective per wire
                                   # bucket instead of one per gradient leaf
    bucket_bytes: Optional[int] = None  # payload cap per bucket (None: one
                                        # bucket for the whole tree)
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


def _leaf_seeds(worker_seed, tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    seeds = [prng.fold_seed(worker_seed, i) for i in range(len(leaves))]
    return jax.tree_util.tree_unflatten(treedef, seeds)


@scoped(FWD_BWD)
def _local_grads(model, params, batch, comp_cfg: CompressionConfig, wseed, local_lr,
                 backend=None):
    """Returns (loss, message_source_tree).

    tau == 1: message source = the raw local gradient (Alg. 1).
    tau > 1 : message source = sum of the tau compressed local steps (Alg. 2);
              batch leaves carry a leading tau axis.
    """
    loss_fn = lambda p, b: model.loss(p, b)[0]
    tau = comp_cfg.local_steps
    if tau == 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads

    local_cfg = engine.local_step_config(comp_cfg)

    def body(carry, c):
        w, acc = carry
        micro = jax.tree_util.tree_map(lambda x: x[c], batch)
        loss, grads = jax.value_and_grad(loss_fn)(w, micro)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        qs = []
        for i, g in enumerate(leaves):
            seed = prng.fold_seed(wseed, 7000 + i)
            q = engine.compress_leaf(g, local_cfg, seed, counter_base=c * g.size,
                                     backend=backend).values
            qs.append(q)
        q_tree = jax.tree_util.tree_unflatten(treedef, qs)
        w = jax.tree_util.tree_map(lambda p, q: p - local_lr * q.astype(p.dtype), w, q_tree)
        acc = jax.tree_util.tree_map(lambda a, q: a + q.astype(jnp.int32), acc, q_tree)
        return (w, acc), loss

    acc0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.int32), params)
    (_, acc), losses = jax.lax.scan(body, (params, acc0), jnp.arange(tau))
    msg_source = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), acc)
    return jnp.mean(losses), msg_source


def build_train_step(model, step_cfg: TrainStepConfig, mesh) -> Callable:
    """Returns jit'd train_step(state, batch) -> (state, metrics)."""
    comp = step_cfg.compression
    axes = tuple(step_cfg.worker_axes)
    backend = engine.resolve_backend(step_cfg.backend)
    # wire negotiation + per-leaf quorum: CompressorSpec/table lookups resolved
    # (and validated) before tracing
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
    quorum_leaves = jax.tree_util.tree_leaves(
        engine.broadcast_quorum(step_cfg.quorum, model.param_shapes()))
    # per-leaf quorum as a FRACTION of realized participation (build-time:
    # bad quorums and q_frac out of (0,1] fail before tracing)
    q_fracs = ([part.resolve_q_frac(q, wire.n_workers) for q in quorum_leaves]
               if part is not None else None)
    # the per-leaf majority vote over the monolithic 2-bit gather decodes
    # the payloads inside the update kernel (engine.server_apply_packed); the
    # ring, bucketed and elastic paths need the decoded vote sum itself
    fused_server = (mode == "votes" and comp.server == "majority_vote"
                    and wire.gathers_packed and part is None
                    and not step_cfg.bucketed)
    if mode != "votes" and any(q != 1 for q in quorum_leaves):
        raise ValueError(
            f"quorum={step_cfg.quorum!r} is a vote-server deadband, but "
            f"compressor {comp.compressor!r} with server {comp.server!r} "
            f"rides the {mode!r} wire where it would be silently ignored; "
            f"use a vote server ({engine.VOTE_SERVERS}) or quorum=1")

    # static bucket layout (bucketed uplink): the whole tree's leaves packed
    # into few wire buckets, offsets row-aligned per the wire's payload format
    plan = None
    if step_cfg.bucketed:
        bucket_fmt = bucketing.wire_bucket_format(mode, wire)
        plan = bucketing.build_bucket_plan(
            jax.tree_util.tree_leaves(model.param_shapes()),
            bucket_fmt,
            bucket_bytes=step_cfg.bucket_bytes,
            # golomb slots are CAPACITY rows — a pure (n, p) function owned
            # by the wire, not a coordinate-count row formula
            rows_fn=(wire.payload_rows if bucket_fmt == "golomb" else None))

    # activation hints may only target auto (non-worker) mesh axes; in pure-DP
    # mode every axis is a worker and no constraints are needed (all compute local)
    act_rules = {k: v for k, v in ACT_RULES_TRAIN.items()
                 if not (isinstance(v, str) and v in axes)}

    def body(state: TrainState, batch):
        with axis_rules(act_rules, mesh):
            return _body_inner(state, batch)

    def _finish(state, treedef, new_leaves, ef_leaves, loss, lr, nnz_acc,
                total, mask, wire_bytes, gather_hbm, fused=0):
        n_workers = collectives.worker_count(axes)
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        new_ef_tree = (jax.tree_util.tree_unflatten(treedef, ef_leaves)
                       if state.ef_residual is not None else None)
        with jax.named_scope(COUNTERS):
            loss_mean = collectives.scalar_psum(loss, axes) / n_workers
            nnz_mean = (collectives.scalar_psum(nnz_acc, axes) / n_workers
                        / jnp.float32(total))
            metrics = {"loss": loss_mean, "lr": lr, "nnz_frac": nnz_mean,
                       "participated": collectives.scalar_psum(
                           mask.astype(jnp.float32), axes),
                       "wire_bytes_per_device": jnp.float32(wire_bytes),
                       "gather_hbm_bytes": jnp.float32(gather_hbm),
                       # share of the coordinates updated by the fused
                       # decode + update kernel (static under jit)
                       "server_fused_share": jnp.float32(fused / total)}
        new_state = TrainState(params=new_params, ef_residual=new_ef_tree,
                               step=state.step + 1, seed=state.seed)
        return new_state, metrics

    def _body_inner(state: TrainState, batch):
        params = state.params
        widx = collectives.worker_index(axes)
        n_workers = collectives.worker_count(axes)
        rseed = sampling.round_seed(state.seed, state.step)
        wseed = prng.fold_seed(rseed, 0x5EED) + widx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        mask = sampling.participation_mask(rseed, state.step, widx, comp.worker_sample_fraction)
        if part is not None:
            # elastic: the round's effective reporting set is the sampled set
            # minus chaos dropouts; w_eff = static weight x report bit is the
            # weight that rides the wire (exact 0.0 for a silent worker)
            mask = mask & sampling.report_mask(rseed, state.step, widx,
                                               part.dropout)
            w_eff = (part.weight_of(widx, n_workers)
                     * mask.astype(jnp.float32))

        loss, msg_src = _local_grads(model, params, batch, comp, wseed,
                                     step_cfg.local_lr, backend=backend)

        leaves, treedef = jax.tree_util.tree_flatten(msg_src)
        new_leaves, ef_leaves = [], []
        ef_flat = (jax.tree_util.tree_leaves(state.ef_residual)
                   if state.ef_residual is not None else [None] * len(leaves))
        p_leaves = jax.tree_util.tree_flatten(params)[0]
        lr = step_cfg.lr(state.step)
        nnz_acc = jnp.float32(0.0)
        total = 0
        fused = 0          # coordinates updated by the fused server kernel
        wire_bytes = 0.0   # per-device uplink ledger (static sizes under jit)
        gather_hbm = 0.0   # peak gather-payload residency (max over exchanges)

        if plan is not None:
            # ---- bucketized uplink: few big collectives -------------------
            # per-leaf compress (seeds/counter_base/budget unchanged — slot
            # payloads are bitwise the per-leaf wire messages), then ONE
            # exchange per bucket; protocol scalars are deduplicated (one
            # n_sel psum, one shared-linf vector pmax for the whole tree)
            n_sel = collectives.scalar_psum(mask.astype(jnp.float32), axes)
            shared_vec = (collectives.worker_shared_linf_many(leaves, axes, mask=mask)
                          if share_linf else None)
            payloads = [None] * len(leaves)
            scales = [None] * len(leaves)
            for b in plan.buckets:
                for s in b.slots:
                    i, g = s.index, leaves[s.index]
                    seed_i = prng.fold_seed(wseed, i)
                    shared = shared_vec[i] if share_linf else None
                    if mode == "decoded":
                        msg = engine.compress_leaf(g, comp, seed_i,
                                                   backend=backend,
                                                   shared_linf=shared)
                        # elastic: the weight premultiplies the decode scale
                        # (w_eff == 1.0 is a bitwise identity; a dropped
                        # worker's slot decodes to exact zeros)
                        sc = msg.scale * w_eff if part is not None else msg.scale
                        dec, nnz = collectives.decoded_message(
                            msg.values, sc, mask,
                            is_ternary=comp.is_ternary)
                        payloads[i] = bucketing.as_rows(dec, plan.fmt, s.rows)
                        nnz_acc += nnz
                    else:
                        msg = engine.compress_leaf_rows(
                            g, comp, seed_i, rows=s.rows, backend=backend,
                            wire=wire, shared_linf=shared)
                        payloads[i] = wire.mask_message(msg.values, mask)
                        nnz_acc += wire.message_nnz(payloads[i])
                        scales[i] = msg.scale
                    total += g.size
            new_leaves = [None] * len(leaves)
            ef_leaves = [None] * len(leaves)
            for b in plan.buckets:
                buf = bucketing.assemble_bucket(
                    [payloads[s.index] for s in b.slots], b, plan.fmt)
                wtots = None
                if mode == "decoded":
                    parts = bucketing.split_bucket(
                        collectives.decoded_exchange_bucket(buf, axes), b)
                    if part is not None:
                        # weights already premultiplied into the psum'd
                        # stream; W (the mean divisor) is one protocol scalar
                        wtots = collectives.scalar_psum(w_eff, axes)
                elif part is not None:
                    # elastic: one weighted exchange per bucket returns
                    # (sum_m w_m payload_m, W) — W is per-slot on the psum
                    # wires (per-coordinate arrays) and one scalar on the
                    # gather wires
                    if mode == "pack8":
                        parts, wtots = wire.exchange_bucket_weighted(
                            buf, b, weight=w_eff,
                            scale=jnp.stack([scales[s.index]
                                             for s in b.slots]))
                    else:
                        parts, wtots = wire.exchange_bucket_weighted(
                            buf, b, weight=w_eff)
                elif mode == "pack8":
                    parts = wire.exchange_bucket(
                        buf, b, scale=jnp.stack([scales[s.index]
                                                 for s in b.slots]))
                else:
                    parts = wire.exchange_bucket(buf, b)
                for j, (s, agg) in enumerate(zip(b.slots, parts)):
                    i = s.index
                    if part is not None:
                        wt = (wtots[j] if isinstance(wtots, (list, tuple))
                              else wtots)
                        if mode == "votes":
                            new_p, new_ef = engine.server_apply(
                                p_leaves[i], agg, comp, lr=lr, ef=ef_flat[i],
                                part_total=wt, q_frac=q_fracs[i],
                                backend=backend)
                        else:
                            new_p, new_ef = engine.server_apply(
                                p_leaves[i], agg, comp, lr=lr, ef=ef_flat[i],
                                n_sel=wt, server="mean",
                                scale=(scales[i] if mode == "scaled_votes"
                                       else None),
                                backend=backend)
                    elif mode == "votes":
                        new_p, new_ef = engine.server_apply(
                            p_leaves[i], agg, comp, lr=lr, ef=ef_flat[i],
                            n_sel=n_sel, quorum=quorum_leaves[i],
                            backend=backend)
                    else:
                        # mean servers: scaled_votes decodes with the ONE
                        # shared scale; pack8/decoded sums arrive dequantized
                        new_p, new_ef = engine.server_apply(
                            p_leaves[i], agg, comp, lr=lr, ef=ef_flat[i],
                            n_sel=n_sel, server="mean",
                            scale=(scales[i] if mode == "scaled_votes" else None),
                            backend=backend)
                    new_leaves[i], ef_leaves[i] = new_p, new_ef
            pay, scal = bucketing.plan_ledger(mode, wire, plan,
                                              share_linf=share_linf)
            wire_bytes = pay + scal
            gather_hbm = bucketing.plan_gather_hbm_bytes(mode, wire, plan)
            return _finish(state, treedef, new_leaves, ef_leaves, loss, lr,
                           nnz_acc, total, mask, wire_bytes, gather_hbm)

        for i, (g, p, ef) in enumerate(zip(leaves, p_leaves, ef_flat)):
            seed_i = prng.fold_seed(wseed, i)
            # ONE ledger definition for both train modes — pinned against the
            # traced collective census by repro.analysis
            wire_bytes += collectives.uplink_ledger(mode, wire, g.size,
                                                    share_linf=share_linf)
            if mode != "decoded":
                gather_hbm = max(gather_hbm, wire.gather_hbm_bytes(g.size))
            shared = None
            if share_linf:
                # TernGrad's magnitude-sharing protocol / linf_share budgets:
                # one f32 pmax over the sampled workers before compressing
                shared = collectives.worker_shared_linf(g, axes, mask=mask)
            if mode != "decoded":
                # wire-native messages (packed uint8 / int8 votes, or int8
                # pack8 levels): one exchange = upload + server sum, then
                # C(.) + SGD fused in the engine. scaled_votes additionally
                # carries ONE shared decode scale (msg.scale) next to the
                # payload; pack8 gathers every worker's scale and dequantizes
                # during the exchange.
                msg = engine.compress_leaf(g, comp, seed_i, backend=backend,
                                           wire=wire, shared_linf=shared)
                votes = wire.mask_message(msg.values, mask)
                nnz_acc += wire.message_nnz(votes)
                n_sel = collectives.scalar_psum(mask.astype(jnp.float32), axes)
                if part is not None:
                    # elastic: weighted exchange returns (sum w_m votes_m, W);
                    # vote servers normalize the deadband to W, mean servers
                    # divide by it
                    if mode == "pack8":
                        wv, wtot = wire.exchange_weighted(
                            votes, g.size, g.shape, weight=w_eff,
                            scale=msg.scale)
                        new_p, new_ef = engine.server_apply(
                            p, wv, comp, lr=lr, ef=ef, n_sel=wtot,
                            server="mean", backend=backend)
                    elif mode == "votes":
                        wv, wtot = wire.exchange_weighted(
                            votes, g.size, g.shape, weight=w_eff)
                        new_p, new_ef = engine.server_apply(
                            p, wv, comp, lr=lr, ef=ef,
                            part_total=wtot, q_frac=q_fracs[i],
                            backend=backend)
                    else:
                        wv, wtot = wire.exchange_weighted(
                            votes, g.size, g.shape, weight=w_eff)
                        new_p, new_ef = engine.server_apply(
                            p, wv, comp, lr=lr, ef=ef, n_sel=wtot,
                            server="mean", scale=msg.scale, backend=backend)
                elif mode == "pack8":
                    dec_sum = wire.exchange(votes, g.size, g.shape,
                                            scale=msg.scale)
                    new_p, new_ef = engine.server_apply(
                        p, dec_sum, comp, lr=lr, ef=ef, n_sel=n_sel,
                        server="mean", backend=backend)
                elif fused_server:
                    new_p = engine.server_apply_packed(
                        p, wire.gather(votes), comp, lr=lr,
                        quorum=quorum_leaves[i], backend=backend)
                    new_ef = ef
                    fused += g.size
                elif mode == "votes":
                    vote_sum = wire.exchange(votes, g.size, g.shape)
                    new_p, new_ef = engine.server_apply(
                        p, vote_sum, comp, lr=lr, ef=ef, n_sel=n_sel,
                        quorum=quorum_leaves[i], backend=backend)
                else:
                    vote_sum = wire.exchange(votes, g.size, g.shape)
                    new_p, new_ef = engine.server_apply(
                        p, vote_sum, comp, lr=lr, ef=ef, n_sel=n_sel,
                        server="mean", scale=msg.scale, backend=backend)
            else:
                msg = engine.compress_leaf(g, comp, seed_i, backend=backend,
                                           shared_linf=shared)
                # decoded-float wire: per-worker-scale ternary baselines
                # (qsgd_1bit/scaled_sign under a mean server) and the float
                # formats ship decode(compress(g)) — fp32 collective bytes,
                # honestly the cost this family pays (identity's message IS
                # g, so D-SGD is bit-identical to raw psum)
                if part is not None:
                    # elastic decoded wire: the weight premultiplies the
                    # decode scale (w_eff == 1.0 is a bitwise identity, a
                    # dropped worker decodes to exact zeros) and the mean
                    # divisor becomes the realized participation W
                    vote_sum, nnz = collectives.decoded_exchange(
                        msg.values, msg.scale * w_eff, mask, axes,
                        is_ternary=comp.is_ternary)
                    n_or_w = collectives.scalar_psum(w_eff, axes)
                else:
                    vote_sum, nnz = collectives.decoded_exchange(
                        msg.values, msg.scale, mask, axes,
                        is_ternary=comp.is_ternary)
                    n_or_w = collectives.scalar_psum(
                        mask.astype(jnp.float32), axes)
                nnz_acc += nnz
                new_p, new_ef = engine.server_apply(
                    p, vote_sum, comp, lr=lr, ef=ef, n_sel=n_or_w,
                    server="mean", backend=backend)
            total += g.size
            new_leaves.append(new_p)
            ef_leaves.append(new_ef)

        return _finish(state, treedef, new_leaves, ef_leaves, loss, lr,
                       nnz_acc, total, mask, wire_bytes, gather_hbm, fused)

    state_spec = P()   # replicated w.r.t. the manual worker axes
    batch_axis = 1 if comp.local_steps > 1 else 0
    def batch_spec(x=None):
        spec = [None] * 4
        spec[batch_axis] = axes if len(axes) > 1 else axes[0]
        return P(*spec[:batch_axis + 1])

    wrapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(state_spec, batch_spec()),
        out_specs=(state_spec, state_spec),
        axis_names=engine.manual_axes(backend, mesh, axes),
        check_vma=False,
    )

    def train_step(state, batch):
        return wrapped(state, batch)

    if step_cfg.donate:
        return jax.jit(train_step, donate_argnums=(0,))
    return jax.jit(train_step)
