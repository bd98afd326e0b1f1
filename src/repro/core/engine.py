"""Backend-dispatching compression/server engine — the one hot path every
consumer (train.step_simple, train.step_streamed, fl.simulation) goes through.

Three backends, bitwise-identical by construction (they share the counter-based
PRNG of ``repro.core.prng``, which the Pallas kernels regenerate in-register):

  pallas    — the fused TPU kernels: the per-compressor compress (and fused
              compress->pack2bit) ops named by the ``CompressorSpec`` registry,
              ``vote_update`` (majority-vote sign + SGD in one pass) and
              ``ef_server`` (fused Eq. 8 scaled-sign error feedback).
  interpret — the same kernels in Pallas interpret mode; runs on CPU and is
              what CI pins against the jnp reference.
  jnp       — the pure-jnp reference compressors/server math. Chunkable leaves
              are compressed in chunks to bound transient RNG buffers (the
              kernels need no chunking — RNG never touches HBM).

Selection: the ``backend=`` argument wins, else the ``REPRO_KERNEL_BACKEND``
env var (``auto|pallas|interpret|jnp``), else ``auto`` = pallas on TPU and jnp
everywhere else. Resolution happens at trace/build time, so a jitted train
step bakes its backend in.

All per-compressor capability questions — which kernel, which wire format,
which scale protocol, which server decode — are answered by the declarative
``CompressorSpec`` table (``repro.core.compressors.SPECS``); this module has
no compressor-name special cases.

Three primitives:

  compress_leaf(g, cfg, seed, counter_base)        — worker uplink Q(g, B)
  server_apply(p, vote_sum, cfg, ...)              — C(.) [+ EF] + SGD update
  server_apply_packed(p, gathered, cfg, ...)       — majority vote + SGD from
                                                     the gathered 2-bit payloads

plus the small shared helpers (vote-server predicates, wire-mode negotiation,
per-leaf quorum broadcasting, local-step config) that keep server-rule and
compressor names out of the train/fl layers entirely.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core.budgets import BudgetConfig, resolve_budget
from repro.core.compressors import (CompressedGrad, CompressorSpec,
                                    chunked_values, get_spec)
from repro.core.scopes import SERVER, UPLINK, scoped
from repro.kernels import common as kcommon
from repro.kernels.ef_server.ops import ef_server_op
from repro.kernels.ef_server.ref import ef_server_ref
from repro.kernels.golomb.ops import golomb_pack_op
from repro.kernels.golomb.ref import golomb_encode_ref
from repro.kernels.pack2bit.ops import pack2bit_op
from repro.kernels.pack2bit.ref import pack2bit_ref
from repro.kernels.vote_update.ops import (vote_update_op,
                                           vote_update_packed2bit_op,
                                           weighted_vote_update_op)
from repro.kernels.vote_update.ref import (vote_update_packed2bit_ref,
                                           vote_update_ref,
                                           weighted_vote_update_ref)

if TYPE_CHECKING:  # avoid a runtime cycle: algorithm imports this module
    from repro.core.algorithm import CompressionConfig

ENV_VAR = "REPRO_KERNEL_BACKEND"
BACKENDS = ("pallas", "interpret", "jnp")

# server rules with a ternary integer vote wire (1-2 B/coord psum); everything
# else ships decoded floats and aggregates by mean
VOTE_SERVERS = ("majority_vote", "scaled_sign_ef")
SERVER_RULES = ("majority_vote", "scaled_sign_ef", "mean")

# how a compressor's messages ride the worker-axis wire (see wire_mode)
WIRE_MODES = ("votes", "scaled_votes", "pack8", "decoded")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Explicit argument > $REPRO_KERNEL_BACKEND > auto (pallas on TPU else jnp)."""
    b = backend if backend is not None else os.environ.get(ENV_VAR, "auto")
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if b not in BACKENDS:
        raise ValueError(f"unknown kernel backend {b!r}; known: {('auto',) + BACKENDS}")
    return b


def manual_axes(backend: str, mesh, worker_axes) -> set:
    """The mesh axes a trainer's shard_map takes manual.

    GSPMD cannot partition a compiled Mosaic kernel, so the pallas backend
    takes every mesh axis manual; the axes beyond the worker axes then hold
    full replicas instead of GSPMD's tensor-parallel split. The other
    backends lower to XLA ops and leave those axes to GSPMD."""
    if backend == "pallas":
        return set(mesh.axis_names)
    return set(worker_axes)


def is_vote_server(cfg: "CompressionConfig") -> bool:
    return cfg.server in VOTE_SERVERS


def needs_server_ef(server: str) -> bool:
    """Does this server rule carry a (server-side) error-feedback residual?"""
    return server == "scaled_sign_ef"


def wire_mode(cfg: "CompressionConfig", vote_impl: Optional[str] = None) -> str:
    """How this (compressor, server, vote_impl) triple's uplink rides the
    worker wire — a pure CompressorSpec table lookup on ``spec.wire_format``:

      votes        — ternary symbols on the integer/packed vote wire, consumed
                     raw by a vote server (majority_vote / scaled_sign_ef).
      scaled_votes — ternary symbols on the integer/packed vote wire plus ONE
                     shared decode scale; the mean server multiplies the vote
                     mean by it. Requires a worker-invariant scale (protocol
                     none or shared_max).
      pack8        — int8 sign*level payload (1 B/coord) plus each worker's
                     f32 decode scale on the all-gather wire; the exchange
                     dequantizes into the mean server's float sum. Needs the
                     gather wire (``vote_impl='allgather_packed'``) — a psum
                     cannot reduce differently-scaled levels on the fabric,
                     so the psum/hier impls fall back to the decoded wire.
      decoded      — decoded float32 messages, psum + mean server (per-worker
                     scales on ternary wires, and the float wire format).

    The mode says what the symbols MEAN on the wire; how ternary symbols are
    *encoded* (flat 2-bit vs the Golomb entropy-coded stream) is the
    orthogonal ``wire_payload_format`` lookup — golomb-format specs ride the
    votes/scaled_votes modes unchanged.
    """
    spec = get_spec(cfg.compressor)
    if spec.wire_format == "float":
        return "decoded"
    if spec.wire_format == "pack8":
        return "pack8" if vote_impl == "allgather_packed" else "decoded"
    if is_vote_server(cfg):
        return "votes"
    return "scaled_votes" if spec.scale_shared else "decoded"


def wire_payload_format(cfg: "CompressionConfig", mode: str,
                        vote_impl: Optional[str] = None) -> str:
    """Which payload format the wire object should speak for this
    (compressor, wire mode, vote_impl) triple — the ``make_vote_wire``
    ``wire_format=`` argument, as a pure ``CompressorSpec`` table lookup.

    The entropy-coded stream needs the gather wire (a fabric psum cannot sum
    variable-length byte streams), so a golomb-format spec on the psum/hier
    impls rides plain int8 votes instead — the golomb twin of pack8's
    fall-back-to-decoded rule, and bitwise-identical votes either way."""
    if mode == "pack8":
        return "pack8"
    spec = get_spec(cfg.compressor)
    if (spec.wire_format == "golomb" and vote_impl == "allgather_packed"
            and mode in ("votes", "scaled_votes")):
        return "golomb"
    return "pack2"


def resolve_golomb_p(cfg: "CompressionConfig",
                     golomb_p: Optional[float] = None) -> float:
    """The plan-time nonzero fraction that sizes the golomb wire's static
    capacity: an explicit setting wins, else a ``target_sparsity`` budget's
    target IS the plan fraction. Anything else is a loud build-time error —
    guessing p would silently mis-size the capacity (overflow truncation or
    a padded wire that loses to pack2)."""
    if golomb_p is not None:
        p = float(golomb_p)
    elif cfg.budget.kind == "target_sparsity":
        p = float(cfg.budget.value)
    else:
        raise ValueError(
            f"the golomb wire needs a plan-time nonzero fraction to size its "
            f"static capacity: set the step config's golomb_p, or use a "
            f"budget of kind 'target_sparsity' (whose target is the plan "
            f"fraction). Budget kind {cfg.budget.kind!r} carries no nnz "
            f"fraction to plan against.")
    if not 0.0 < p < 1.0:
        raise ValueError(f"golomb plan fraction must be in (0,1), got {p}")
    return p


def resolve_ring_chunk_rows(ring_chunk_rows: Optional[int],
                            vote_impl: Optional[str]) -> Optional[int]:
    """Negotiate the ring-pipelined gather knob at step-build time: ``None``
    stays monolithic (the default), anything else must pair with the gather
    impl and be a positive sublane multiple. The psum/hier impls reduce on
    the fabric and never materialize a gathered tensor, so a ring request
    there is a configuration contradiction, not something to silently drop —
    mirror the wire_mode fallbacks' policy of failing loudly instead of
    misreporting the byte/HBM ledger."""
    if ring_chunk_rows is None:
        return None
    if vote_impl != "allgather_packed":
        raise ValueError(
            f"ring_chunk_rows={ring_chunk_rows!r} needs "
            f"vote_impl='allgather_packed' (the ring chunks a gathered "
            f"payload; vote_impl={vote_impl!r} has none) — drop the ring "
            f"knob or switch the vote wire")
    from repro.kernels import common as kcommon
    r = int(ring_chunk_rows)
    if r <= 0 or r % kcommon.SUBLANE_PAD != 0:
        raise ValueError(
            f"ring_chunk_rows must be a positive multiple of the sublane "
            f"tile ({kcommon.SUBLANE_PAD}), got {ring_chunk_rows!r} — see "
            f"collectives.DEFAULT_RING_CHUNK_ROWS for the documented default")
    return r


def check_participation_server(server: str, compressor: str) -> None:
    """Build-time gate for elastic participation: the weighted,
    participation-normalized vote family covers the majority-vote deadband
    (``|sum w_m sign_m| >= q_frac * W``) and the mean server (divide by the
    realized participation ``W`` instead of ``|S|``). ``scaled_sign_ef``
    keeps a server-side error-feedback residual whose scale calibration
    assumes the full fleet's mean delta — silently re-normalizing it to a
    shifting reporting set would corrupt the residual, so it must fail HERE,
    at step build, not mid-run."""
    if server == "scaled_sign_ef":
        raise ValueError(
            f"elastic participation (a ParticipationSpec) is incompatible "
            f"with server 'scaled_sign_ef' (compressor {compressor!r}): the "
            f"server-side EF residual is calibrated against the full fleet's "
            f"mean delta and cannot be participation-normalized per round. "
            f"Use server='majority_vote' or 'mean'.")


def needs_shared_linf(cfg: "CompressionConfig") -> bool:
    """Must the trainer all-reduce(max) the worker L-inf norms before
    compressing? True for the shared_max scale protocol (TernGrad's magnitude
    sharing) and the linf_share budget policy."""
    return (get_spec(cfg.compressor).scale_protocol == "shared_max"
            or cfg.budget.kind == "linf_share")


def local_budget_value(cfg: "CompressionConfig") -> float:
    """B_l for the tau inner steps of Alg. 2.

    Precedence: cfg.local_budget > cfg.budget.local_value > the uplink B
    itself when the budget is a fixed magnitude (the paper's B_l=10/B_g=1
    regime) > 1.0. Non-fixed budget kinds (target_sparsity etc.) never leak
    their ``value`` into B_l — it is not a magnitude there.
    """
    if cfg.local_budget is not None:
        return float(cfg.local_budget)
    if cfg.budget.local_value is not None:
        return float(cfg.budget.local_value)
    return float(cfg.budget.value) if cfg.budget.kind == "fixed" else 1.0


def local_step_config(cfg: "CompressionConfig") -> "CompressionConfig":
    """Config for the inner (Alg. 2) local steps: sparsign at fixed B_l."""
    return dataclasses.replace(
        cfg, compressor="sparsign",
        budget=BudgetConfig(kind="fixed", value=local_budget_value(cfg)),
        local_steps=1)


# ---------------------------------------------------------------------------
# Per-leaf quorum
# ---------------------------------------------------------------------------

def broadcast_quorum(quorum, like_tree):
    """Widen the server quorum deadband to a per-leaf tree.

    ``quorum`` is either a positive int (broadcast to every leaf) or a pytree
    *prefix* of ``like_tree`` (e.g. ``{"embed": 3, "blocks": 1, ...}`` against a
    parameter dict) whose leaves are positive ints. Returns a tree matching
    ``like_tree`` exactly, validated eagerly — step builders call this at build
    time so a malformed quorum tree fails before tracing, not mid-run.
    """
    def check(q):
        if isinstance(q, bool) or not isinstance(q, int) or q < 1:
            raise ValueError(
                f"quorum entries must be ints >= 1, got {q!r} ({type(q).__name__})")
        return q

    if isinstance(quorum, int) and not isinstance(quorum, bool):
        check(quorum)
        return jax.tree_util.tree_map(lambda _: quorum, like_tree)
    qdef = jax.tree_util.tree_structure(quorum)
    try:
        subtrees = qdef.flatten_up_to(like_tree)
    except ValueError as e:
        raise ValueError(
            f"quorum tree is not a prefix of the parameter tree: {e}") from None
    out = [jax.tree_util.tree_map(lambda _, q=check(q): q, sub)
           for q, sub in zip(jax.tree_util.tree_leaves(quorum), subtrees)]
    return jax.tree_util.tree_unflatten(qdef, out)


# ---------------------------------------------------------------------------
# Worker-side primitive
# ---------------------------------------------------------------------------

@scoped(UPLINK)
def compress_leaf(
    g: jnp.ndarray,
    cfg: "CompressionConfig",
    seed,
    counter_base=0,
    *,
    shared_linf=None,
    backend: Optional[str] = None,
    wire=None,
) -> CompressedGrad:
    """Q(g, B): one worker's uplink message for a single tensor leaf.

    Dispatch is a ``CompressorSpec`` lookup: compressors with a registered
    Pallas op take the fused kernel on the pallas/interpret backends (RNG
    regenerated in-register — no chunking needed at any size); everything
    else, and the jnp backend, runs the normalized reference path (chunked for
    the counter-indexed families).

    ``shared_linf`` is the psum-max'd worker L-inf (``needs_shared_linf``):
    it feeds both the ``linf_share`` budget policy and the ``shared_max``
    scale protocol (TernGrad's magnitude sharing).

    ``wire`` (a ``repro.dist.collectives.VoteWire``, or None) selects the
    message's *wire-native* format (``wire.native_format``, validated against
    the spec's declared ``wire_format``). When the wire wants a packed format
    — 2-bit codes or the Golomb entropy-coded stream for ternary
    compressors, int8 sign*level for pack8 —
    ``values`` is the packed canonical view, produced in one fused pass
    (gradient -> wire bytes, no int8 ternary / int32 level tensor in HBM)
    when the spec registers a ``fused_pack_op``, else compressed then packed.
    The bytes are identical either way; only the number of HBM round-trips
    differs. Scale-carrying compressors return their decode scale in
    ``msg.scale`` alongside the (packed) payload.
    """
    backend = resolve_backend(backend)
    spec: CompressorSpec = get_spec(cfg.compressor)
    if shared_linf is None and needs_shared_linf(cfg):
        mapped = jax.sharding.get_abstract_mesh().manual_axes
        if mapped:
            raise ValueError(
                f"compressor {cfg.compressor!r} needs the magnitude-shared "
                f"worker L-inf (scale protocol "
                f"{spec.scale_protocol!r} / budget kind {cfg.budget.kind!r}) "
                f"but compress_leaf was called inside a mapped context (axes "
                f"{sorted(mapped)}) without shared_linf=. Degrading to the "
                f"per-worker local norm here would silently give every worker "
                f"its own TernGrad normalizer — the exact drift the sharing "
                f"protocol exists to kill. Reduce "
                f"collectives.worker_shared_linf over the worker axes and "
                f"pass it; the local-norm fallback is only valid for the "
                f"single-worker public API outside a mesh.")
    budget = resolve_budget(cfg.budget, g, shared_linf=shared_linf)
    scale = spec.resolve_scale(g, shared_linf=shared_linf)
    param = budget if scale is None else scale
    msg_scale = jnp.float32(1.0) if scale is None else scale.astype(jnp.float32)
    wire_fmt = wire.native_format if wire is not None else None
    want_packed = wire_fmt in ("pack2", "golomb", "pack8")
    if want_packed and spec.wire_format != wire_fmt:
        raise ValueError(
            f"the {wire_fmt!r} wire carries "
            f"{'int8 sign*level' if wire_fmt == 'pack8' else 'ternary'} "
            f"messages only; compressor {cfg.compressor!r} declares wire "
            f"format {spec.wire_format!r}")
    interpret = backend == "interpret"
    # the golomb wire's static capacity is sized by its plan-time nonzero
    # fraction — the fused/two-pass encoders must use the SAME p or the
    # payload shape disagrees with the wire ledger at trace time (loudly)
    fused_kwargs = {"p": wire.p} if wire_fmt == "golomb" else {}
    if backend != "jnp" and spec.pallas_op is not None:
        if want_packed and spec.fused_pack_op is not None:
            packed = spec.fused_pack_op(g, param, seed, counter_base,
                                        interpret=interpret, **fused_kwargs)
            return CompressedGrad(values=packed, scale=msg_scale)
        vals = spec.pallas_op(g, param, seed, counter_base, interpret=interpret)
    elif spec.chunkable:
        vals = chunked_values(spec.values, g, param, seed, counter_base)
    else:
        vals = spec.values(g, param, seed, counter_base)
    if want_packed:
        # two-pass fallback (specs without a fused kernel, and the jnp
        # reference backend): same wire bytes, one extra round-trip
        if wire_fmt == "pack8":
            # the pack8 payload IS the canonical int8 view of the levels
            view, _ = kcommon.to_2d(vals.reshape(-1))
            return CompressedGrad(values=view, scale=msg_scale)
        if wire_fmt == "golomb":
            if backend == "jnp":
                packed = golomb_encode_ref(vals, p=wire.p)
            else:
                packed = golomb_pack_op(vals, p=wire.p, interpret=interpret)
            return CompressedGrad(values=packed, scale=msg_scale)
        if backend == "jnp":
            view, _ = kcommon.to_2d(vals.reshape(-1))
            packed = pack2bit_ref(view)
        else:
            packed = pack2bit_op(vals, interpret=interpret)
        return CompressedGrad(values=packed, scale=msg_scale)
    return CompressedGrad(values=vals, scale=msg_scale)


@scoped(UPLINK)
def compress_leaf_rows(
    g: jnp.ndarray,
    cfg: "CompressionConfig",
    seed,
    counter_base=0,
    *,
    rows: int,
    shared_linf=None,
    backend: Optional[str] = None,
    wire=None,
) -> CompressedGrad:
    """``compress_leaf`` straight into a bucket slice: the wire-native message
    reshaped/trimmed to exactly ``rows`` canonical payload rows (the leaf's
    ``bucketing.LeafSlot`` slice). The compression itself — seeds,
    counter_base, budget/scale resolution — is byte-identical to the per-leaf
    path; only the buffer layout changes (packed canonical views drop their
    per-leaf sublane zero-pad rows, leaf-shaped votes pad into rows), so a
    slot's payload is bitwise the per-leaf wire message."""
    from repro.dist import bucketing  # lazy: dist layers import this module
    msg = compress_leaf(g, cfg, seed, counter_base, shared_linf=shared_linf,
                        backend=backend, wire=wire)
    return CompressedGrad(
        values=bucketing.as_rows(msg.values, wire.native_format, rows),
        scale=msg.scale)


# ---------------------------------------------------------------------------
# Server-side primitive
# ---------------------------------------------------------------------------

@scoped(SERVER)
def server_apply(
    p: jnp.ndarray,
    vote_sum: jnp.ndarray,
    cfg: "CompressionConfig",
    *,
    lr,
    ef=None,
    n_sel=None,
    server: Optional[str] = None,
    scale=None,
    leaf_size: Optional[int] = None,
    l1_reduce: Optional[Callable] = None,
    quorum: int = 1,
    part_total=None,
    q_frac: Optional[float] = None,
    backend: Optional[str] = None,
):
    """C(sum of worker messages) [+ EF] + SGD for one leaf (or leaf shard).

    Returns ``(new_p, new_ef)`` with ``new_p`` in ``p.dtype``.

    - ``majority_vote``:  p - lr * sign(vote_sum); integer votes take the fused
      ``vote_update`` kernel on the pallas/interpret backends. ``ef`` passes
      through untouched.
    - ``scaled_sign_ef``: acc = vote_sum/n_sel + ef; scale = ||acc||_1/leaf_size
      (``l1_reduce`` hook lets streamed mode psum the partial L1 across FSDP
      shards); update = scale*sign(acc) via the fused ``ef_server`` kernel;
      new_ef = acc - update.
    - ``mean``:           p - lr * scale * vote_sum/n_sel. ``vote_sum`` is the
      sum of decoded float messages (the per-worker-scale wire, ``scale``
      None/1) or the raw ternary vote sum with ``scale`` the shared decode
      scale (the ``scaled_votes`` wire — TernGrad's magnitude-shared s_t).

    ``server`` overrides ``cfg.server`` (the non-ternary baselines always
    aggregate by mean regardless of the configured rule).

    Elastic participation (``part_total`` + ``q_frac``): ``vote_sum`` is the
    WEIGHTED f32 vote ``sum_m w_m * votes_m`` from the wire's weighted
    exchange and ``part_total`` the realized participation
    ``W = sum_reporting w_m`` (scalar, or per-coordinate on the psum wires).
    The majority-vote deadband normalizes to it: no step unless
    ``|vote_sum| >= q_frac * W`` (the fused ``weighted_vote_update`` kernel).
    Mean servers instead pass ``part_total`` as ``n_sel`` — the divisor IS
    the realized participation. ``scaled_sign_ef`` rejects elastic input
    (``check_participation_server`` — also enforced at step build).
    """
    backend = resolve_backend(backend)
    rule = server if server is not None else cfg.server
    lr = jnp.asarray(lr, jnp.float32)

    if part_total is not None:
        check_participation_server(rule, cfg.compressor)

    if rule == "majority_vote":
        if part_total is not None:
            if q_frac is None:
                raise ValueError(
                    "elastic majority vote needs q_frac (the quorum as a "
                    "fraction of realized participation) next to part_total")
            wv = vote_sum.astype(jnp.float32)
            if backend != "jnp":
                new_p = weighted_vote_update_op(
                    p, wv, part_total, lr, q_frac=float(q_frac),
                    interpret=(backend == "interpret"))
            else:
                new_p = weighted_vote_update_ref(p, wv, part_total, lr,
                                                 q_frac=float(q_frac))
            return new_p, ef
        if jnp.issubdtype(vote_sum.dtype, jnp.integer):
            if backend != "jnp":
                new_p = vote_update_op(p, vote_sum, lr, quorum=quorum,
                                       interpret=(backend == "interpret"))
            else:
                new_p = vote_update_ref(p, vote_sum, lr, quorum=quorum)
        else:
            # float votes (decoded-sum wire, e.g. the FL sim): sign directly —
            # the int-vote kernel/oracle would truncate fractional sums
            v = vote_sum
            step = (jnp.where(jnp.abs(v) >= quorum, jnp.sign(v), 0) if quorum > 1
                    else jnp.sign(v)).astype(jnp.float32)
            new_p = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
        return new_p, ef

    if rule == "mean":
        assert n_sel is not None, "mean server needs n_sel (|S|)"
        upd = vote_sum.astype(jnp.float32) / jnp.maximum(jnp.asarray(n_sel, jnp.float32), 1.0)
        if scale is not None:
            upd = upd * jnp.asarray(scale, jnp.float32)
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), ef

    if rule == "scaled_sign_ef":
        assert ef is not None and n_sel is not None, "scaled_sign_ef needs ef + n_sel"
        mean_delta = vote_sum.astype(jnp.float32) / jnp.maximum(
            jnp.asarray(n_sel, jnp.float32), 1.0)
        eff = ef.astype(jnp.float32)
        part = jnp.sum(jnp.abs(mean_delta + eff))
        if l1_reduce is not None:
            part = l1_reduce(part)
        size = leaf_size if leaf_size is not None else mean_delta.size
        srv_scale = part / jnp.float32(size)
        if backend != "jnp":
            upd, new_ef = ef_server_op(mean_delta, eff, srv_scale,
                                       interpret=(backend == "interpret"))
        else:
            upd, new_ef = ef_server_ref(mean_delta, eff, srv_scale)
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), new_ef

    raise ValueError(f"unknown server rule {rule!r}; known: {SERVER_RULES}")


@scoped(SERVER)
def server_apply_packed(
    p: jnp.ndarray,
    gathered: jnp.ndarray,
    cfg: "CompressionConfig",
    *,
    lr,
    quorum: int = 1,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Majority vote + SGD for one leaf, straight from the all-gather wire's
    (M, rows, LANES//4) 2-bit payloads (``PackedVoteWire.gather``).

    The pallas/interpret backends take the fused ``vote_update_packed2bit``
    kernel: the payloads are decoded and summed in VMEM, so no vote sum
    reaches HBM. The jnp backend runs the unfused reference chain (decode-sum,
    then ``vote_update_ref``). Both are bitwise ``server_apply`` of the
    wire's ``exchange``. Returns ``new_p`` in ``p.dtype``."""
    backend = resolve_backend(backend)
    if cfg.server != "majority_vote":
        raise ValueError(
            f"the fused packed update is the integer majority vote; server "
            f"{cfg.server!r} needs the decoded vote sum (server_apply)")
    lr = jnp.asarray(lr, jnp.float32)
    if backend == "jnp":
        return vote_update_packed2bit_ref(p, gathered, lr, quorum=quorum)
    return vote_update_packed2bit_op(p, gathered, lr, quorum=quorum,
                                     interpret=(backend == "interpret"))
