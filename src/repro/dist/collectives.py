"""Worker-axis collectives for the vote exchange (Algorithm 1 step 3), and the
``VoteWire`` abstraction every hot-path consumer speaks.

The paper's M workers are the devices along the mesh worker axes ('pod',
'data'). Each worker holds a ternary message per gradient leaf; the server sum
is a collective over those axes, computed redundantly on every worker so the
downlink is free. Three wire-equivalent variants:

- ``vote_psum``:             one integer psum — the production default.
- ``vote_psum_hier``:        two-level psum (int8 within a pod, widened
                             across pods) matching the hierarchical wire
                             model in benchmarks/bench_collectives.py.
- ``vote_allgather_packed``: all-gather of 2-bit-packed votes (the
                             kernels/pack2bit wire format) + fused local
                             decode-sum; costs M*d/4 bytes on the wire, honest
                             about the "no integer reduction on the fabric"
                             regime.

All three return the same per-coordinate vote total; the equivalence is
pinned by tests/mdev/check_collectives.py on a forced 8-device host mesh and
by tests/mdev/check_wires.py at the train-step level.

Sparse ternary messages can also ride the sub-2-bit entropy-coded gather
(``GolombWire``, wire format ``golomb``): Golomb/RLE-coded zero runs + sign
bits at a static plan-time capacity (kernels/golomb), ~(2+b)*p bits/coord at
plan nonzero fraction p vs pack2's flat 2 — same integer vote totals, a
fraction of the bytes at paper-regime sparsity.

Non-ternary 8-bit payloads (qsgd8's sign*level stream, wire format ``pack8``)
get their own gather-wire twin, ``vote_allgather_packed8``/``Pack8Wire``:
1 B/coord plus each worker's 4-B decode scale, dequantized into the mean
server's float sum during the fused decode — the honest FedCom-baseline wire
(vs 4 B/coord decoded psum). There is no psum variant: a fabric reduction
cannot sum levels quantized against different norms.

``make_vote_wire(impl, axes, mesh, wire_format=)`` builds the wire object at
step-build time. A wire knows its *native message format* (``native_format``:
``int8`` leaf-shaped ternary votes, ``pack2`` 2-bit canonical view, or
``pack8`` int8 level canonical view — what ``engine.compress_leaf(wire=...)``
emits), how to mask/count/exchange messages in that format, and its
per-round per-device wire-byte ledger (``wire_bytes``), computed from the real
buffer sizes (including canonical-view padding), not an idealized model.

Scale-carrying compressors ship f32 decode scales next to the payload: one
shared scalar for the ``scaled_votes`` mode (``worker_shared_linf`` is the
magnitude-sharing all-reduce(max) that produces it), per-worker scalars on
the pack8 wire; ``VoteWire.scalar_bytes`` is the ledger entry either way.

Ring-pipelined gather (``ring_chunk_rows``): the gather wires' default
exchange is one monolithic ``all_gather`` that materializes the full
``(M, rows, width)`` tensor in HBM before decoding. Setting
``ring_chunk_rows`` replaces it with an M-1-hop ``ring_permute`` pipeline:
the payload is cut into fixed-shape row chunks, each chunk circulates the
worker ring with every arriving slice decode-summed immediately through the
same fused kernels, so peak payload HBM is ~2 chunks (in-flight + decoding)
instead of M x payload. Total fabric bytes are unchanged — every byte still
visits every worker — only the residency changes; ``gather_hbm_bytes`` is
the ledger entry. Integer wires (pack2, golomb) accumulate int32 and are
bitwise-equal to the monolithic gather at any arrival order; the pack8
wire's f32 sums associate in ring-arrival order (self, prev, prev-1, ...)
instead of worker-index order — deterministic, allclose vs the oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.scopes import COUNTERS, EXCHANGE, SERVER, UPLINK, scoped


VOTE_IMPLS = ("psum", "hier", "allgather_packed")


@dataclasses.dataclass(frozen=True)
class ParticipationSpec:
    """Elastic-participation contract a ``VoteWire`` carries: per-worker vote
    weights (FedCom-style data-volume weighting), a quorum expressed as a
    FRACTION of realized participation, and a per-round report-dropout rate
    (chaos: crashes / stragglers past the round deadline).

    With a spec attached, the wire's weighted exchange returns
    ``sum_m w_m * votes_m`` together with the realized participation total
    ``W = sum_{reporting} w_m``, and the server deadband becomes
    ``|sum w_m sign_m| >= q_frac * W`` instead of a fixed integer M-quorum —
    the vote normalizes to whoever actually reported. ``weights=None`` means
    uniform 1.0; ``q_frac=None`` re-derives the fraction from the legacy
    integer quorum (``resolve_q_frac``). Validation is loud and build-time."""

    weights: Optional[Tuple[float, ...]] = None
    q_frac: Optional[float] = None
    dropout: float = 0.0

    def __post_init__(self):
        if self.weights is not None:
            w = tuple(float(x) for x in self.weights)
            if not w or any(not (x > 0.0) or not (x < float("inf")) for x in w):
                raise ValueError(
                    f"participation weights must be positive finite floats "
                    f"(a zero/negative weight is a permanently-dead worker — "
                    f"shrink the mesh instead), got {self.weights!r}")
            object.__setattr__(self, "weights", w)
        if self.q_frac is not None:
            q = float(self.q_frac)
            if not (0.0 < q <= 1.0):
                raise ValueError(
                    f"quorum fraction must be in (0, 1]: it is the share of "
                    f"realized participation the vote magnitude must clear, "
                    f"got {self.q_frac!r}")
        d = float(self.dropout)
        if not (0.0 <= d < 1.0):
            raise ValueError(
                f"report dropout must be in [0, 1) (1.0 would drop every "
                f"report every round), got {self.dropout!r}")

    @property
    def is_uniform(self) -> bool:
        return self.weights is None

    def weights_array(self, n_workers: int) -> jnp.ndarray:
        """(M,) f32 per-worker weights (uniform 1.0 when unset), validated
        against the wire's worker count."""
        if self.weights is None:
            return jnp.ones((n_workers,), jnp.float32)
        if len(self.weights) != n_workers:
            raise ValueError(
                f"participation weights cover {len(self.weights)} workers "
                f"but the wire has {n_workers}")
        return jnp.asarray(self.weights, jnp.float32)

    def weight_of(self, widx, n_workers: int) -> jnp.ndarray:
        """This worker's static weight as a traced f32 scalar (flat worker
        index — the same row-major order as ``worker_index``)."""
        if self.weights is None:
            return jnp.float32(1.0)
        return self.weights_array(n_workers)[widx]

    def resolve_q_frac(self, quorum: int, n_workers: int) -> float:
        """The wire's quorum fraction: the explicit ``q_frac``, else the
        legacy integer M-quorum re-derived as ``quorum / M`` — at full
        uniform participation (W = M) the weighted deadband
        ``|v| >= q_frac * W`` is then exactly the legacy ``|v| >= quorum``."""
        if self.q_frac is not None:
            return float(self.q_frac)
        q = int(quorum)
        if not (1 <= q <= n_workers):
            raise ValueError(
                f"cannot derive a quorum fraction: integer quorum {quorum!r} "
                f"is outside [1, M={n_workers}]")
        return q / float(n_workers)


def worker_count(axes: Sequence[str]) -> int:
    """M = product of the worker-axis sizes (static)."""
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def worker_index(axes: Sequence[str]) -> jnp.ndarray:
    """This worker's flat index in [0, M): row-major over ``axes`` order."""
    idx = None
    for a in axes:
        i = jax.lax.axis_index(a)
        idx = i if idx is None else idx * jax.lax.axis_size(a) + i
    return idx


def _sum_dtype(n_workers: int):
    """Smallest int dtype holding ternary-vote sums in [-M, M] — the psum
    payload dtype IS the wire format, so don't widen beyond need."""
    if n_workers <= 127:
        return jnp.int8
    if n_workers <= 32767:
        return jnp.int16
    return jnp.int32


def packed_nbytes(n_coords: int) -> int:
    """Actual bytes of the 2-bit packed wire for an n-coordinate leaf: the
    canonical (rows, LANES) view is padded to the sublane tile, and the padded
    rows ship. This is the *real* per-worker payload (vs the idealized d/4)."""
    from repro.kernels import common as kcommon
    return kcommon.canonical_rows(n_coords) * (kcommon.LANES // 4)


def packed8_nbytes(n_coords: int) -> int:
    """Actual bytes of the pack8 wire for an n-coordinate leaf: the canonical
    (rows, LANES) int8 view, padded rows included — 1 B/coord at aligned
    sizes (vs the idealized d)."""
    from repro.kernels import common as kcommon
    return kcommon.canonical_rows(n_coords) * kcommon.LANES


def golomb_payload_nbytes(n_coords: int, p: float) -> int:
    """Actual bytes of the entropy-coded golomb wire for an n-coordinate leaf
    at plan-time nonzero fraction p: the static capacity rows (header +
    six-sigma coded-bit bound, ``kernels.golomb.ref.golomb_rows``) — capacity
    padding billed honestly, exactly what the fixed-shape gather ships."""
    from repro.kernels.golomb import ref as golomb_ref
    return golomb_ref.golomb_nbytes(n_coords, p)


def vote_psum(votes: jnp.ndarray, axes: Sequence[str], n_workers: int) -> jnp.ndarray:
    """Integer psum of ternary votes over the worker axes."""
    return jax.lax.psum(votes.astype(_sum_dtype(int(n_workers))), tuple(axes))


def scalar_psum(x: jnp.ndarray, axes) -> jnp.ndarray:
    """Sanctioned all-reduce for O(1) protocol/metric scalars (loss, nnz,
    participation counts, scaled-sign shard L1 partials). Raw ``lax.psum``
    outside this module is a repolint error — array payloads must ride a
    ``VoteWire`` (or ``decoded_exchange``) so the byte ledger sees them; a
    scalar reduction is protocol traffic the ledger deliberately does not
    bill, and routing it here keeps that distinction auditable."""
    return jax.lax.psum(x, axes if isinstance(axes, str) else tuple(axes))


def fsdp_all_gather(leaf: jnp.ndarray, axis_name: str, axis: int, *,
                    tiled: bool = True) -> jnp.ndarray:
    """Sanctioned all-gather for FSDP parameter unsharding (streamed mode's
    per-superblock param regather). Not uplink traffic — it moves parameters,
    not gradient messages — so it is billed by the FSDP gather model in
    benchmarks/bench_collectives.py, not the VoteWire ledger; keeping the raw
    collective here (and only here) lets the repolint distinguish the two."""
    return jax.lax.all_gather(leaf, axis_name, axis=axis, tiled=tiled)


def worker_shared_linf(g: jnp.ndarray, axes: Sequence[str], mask=None) -> jnp.ndarray:
    """max_m ||g_m||_inf over the worker axes — TernGrad's magnitude-sharing
    protocol (one f32 scalar all-reduce(max), ~4 B on the fabric) and the
    ``linf_share`` budget policy's shared statistic. Must run inside the
    worker-axes shard_map. ``mask`` (scalar bool) excludes non-participating
    workers from the max, matching the round's sampled set S."""
    local = jnp.max(jnp.abs(g.astype(jnp.float32)))
    if mask is not None:
        local = jnp.where(mask, local, 0.0)
    return jax.lax.pmax(local, tuple(axes))


def worker_shared_linf_many(gs: Sequence[jnp.ndarray], axes: Sequence[str],
                            mask=None) -> jnp.ndarray:
    """Vectorized ``worker_shared_linf``: ONE (L,) f32 pmax for L leaves
    instead of L scalar pmaxes — the bucketed path's magnitude-sharing
    protocol. pmax is element-wise, so entry i is bitwise the per-leaf
    ``worker_shared_linf(gs[i], ...)``."""
    local = jnp.stack([jnp.max(jnp.abs(g.astype(jnp.float32))) for g in gs])
    if mask is not None:
        local = jnp.where(mask, local, 0.0)
    return jax.lax.pmax(local, tuple(axes))


def vote_psum_hier(votes: jnp.ndarray, inner_axis: str, outer_axis: str,
                   inner_size: int, outer_size: int) -> jnp.ndarray:
    """Two-level vote sum: int8-narrow within the fast inner domain ('data',
    intra-pod ICI), widened only for the slow outer hop ('pod', DCN). Equal to
    the flat psum; the wire ledger differs (1 B/coord inner + 2 B/coord outer
    vs 1-4 B/coord flat, cf. bench_collectives.wire_model)."""
    inner = jax.lax.psum(votes.astype(_sum_dtype(int(inner_size))), inner_axis)
    total = int(inner_size) * int(outer_size)
    return jax.lax.psum(inner.astype(_sum_dtype(total)), outer_axis)


def vote_allgather_packed(votes: jnp.ndarray, axes: Sequence[str],
                          n_workers: int, *, backend: Optional[str] = None) -> jnp.ndarray:
    """All-gather of 2-bit-packed votes + fused local decode-sum.

    Wire bytes = M * ceil(d/4) per device (vs the psum's reduced payload) —
    the trade the paper's Table reports for fabrics without int reductions.
    Packing uses the pack2bit kernel's canonical block-interleaved format; the
    decode side is the fused unpack+accumulate kernel (``unpack2bit_sum_op``),
    so the (M, rows, LANES) int8 ternary tensor never materializes —
    ``backend="jnp"`` selects the vmapped oracle instead.
    """
    from repro.kernels.pack2bit.ops import pack2bit_op

    interpret = (backend == "interpret") if backend is not None else None
    packed = pack2bit_op(votes.astype(jnp.int8), interpret=interpret)
    total = _packed_decode_sum(
        jax.lax.all_gather(packed, tuple(axes), axis=0, tiled=False),
        votes.size, votes.shape, backend=backend)
    return total.astype(_sum_dtype(int(n_workers)))


@scoped(SERVER)
def _packed_decode_sum(gathered: jnp.ndarray, size: int, shape,
                       *, backend: Optional[str]) -> jnp.ndarray:
    """(M, rows, q) gathered packed votes -> int32 vote sum in ``shape``,
    dispatched like the engine: jnp -> vmapped oracle, else fused kernel."""
    from repro.kernels import common as kcommon
    from repro.kernels.pack2bit.ops import unpack2bit_sum_op
    from repro.kernels.pack2bit.ref import unpack2bit_sum_ref

    if backend == "jnp":
        return kcommon.from_2d(unpack2bit_sum_ref(gathered), size, shape)
    interpret = (backend == "interpret") if backend is not None else None
    return unpack2bit_sum_op(gathered, size, shape, interpret=interpret)


@scoped(SERVER)
def _golomb_decode_sum(gathered: jnp.ndarray, size: int, shape, *, p: float,
                       backend: Optional[str]) -> jnp.ndarray:
    """(M, rows, 128) gathered entropy-coded payloads -> int32 vote sum in
    ``shape``, dispatched like the engine: jnp -> the reference decoder
    (bitwise the kernel — shared helpers), else the fused decode-sum kernel."""
    from repro.kernels.golomb.ops import ungolomb_sum_op
    from repro.kernels.golomb.ref import ungolomb_sum_ref

    if backend == "jnp":
        return ungolomb_sum_ref(gathered, size, shape, p=p)
    interpret = (backend == "interpret") if backend is not None else None
    return ungolomb_sum_op(gathered, size, shape, p=p, interpret=interpret)


@scoped(SERVER)
def _packed_decode_wsum(gathered: jnp.ndarray, weights: jnp.ndarray,
                        size: int, shape,
                        *, backend: Optional[str]) -> jnp.ndarray:
    """Weighted twin of ``_packed_decode_sum``: (M, rows, q) gathered packed
    votes + (M,) f32 per-worker weights -> f32 ``sum_m w_m * votes_m`` in
    ``shape``. A masked-out worker's all-zero payload decodes to zero votes
    AND its weight is zero, so it contributes exact zeros twice over."""
    from repro.kernels import common as kcommon
    from repro.kernels.pack2bit.ops import unpack2bit_wsum_op
    from repro.kernels.pack2bit.ref import unpack2bit_wsum_ref

    if backend == "jnp":
        return kcommon.from_2d(unpack2bit_wsum_ref(gathered, weights), size, shape)
    interpret = (backend == "interpret") if backend is not None else None
    return unpack2bit_wsum_op(gathered, weights, size, shape, interpret=interpret)


@scoped(SERVER)
def _golomb_decode_wsum(gathered: jnp.ndarray, weights: jnp.ndarray,
                        size: int, shape, *, p: float,
                        backend: Optional[str]) -> jnp.ndarray:
    """Weighted twin of ``_golomb_decode_sum``: f32 ``sum_m w_m * votes_m``
    with per-worker weights riding the gather as the side channel."""
    from repro.kernels.golomb.ops import ungolomb_wsum_op
    from repro.kernels.golomb.ref import ungolomb_wsum_ref

    if backend == "jnp":
        return ungolomb_wsum_ref(gathered, weights, size, shape, p=p)
    interpret = (backend == "interpret") if backend is not None else None
    return ungolomb_wsum_op(gathered, weights, size, shape, p=p,
                            interpret=interpret)


def _unpack8_op():
    """Lazy accessor for the fused pack8 decode-sum op, traced under the
    server scope (kernels import at call time, like every other kernel
    dispatch in this module)."""
    from repro.kernels.pack8.ops import unpack8_sum_op
    return scoped(SERVER)(unpack8_sum_op)


@scoped(UPLINK)
def decoded_message(values: jnp.ndarray, scale, mask, *, is_ternary: bool):
    """One worker's ``decoded``-mode message: decode locally (values * scale),
    zero non-participants. Returns ``(decoded fp32 message, masked nnz)`` —
    ternary messages count |symbols|, float payloads count nonzero decoded
    coordinates. Shared by the per-leaf psum (``decoded_exchange``) and the
    bucketed path (which assembles many decoded messages into one psum), so
    the bitwise pin between them depends on ONE decode definition."""
    dec = values.astype(jnp.float32) * scale
    dec = jnp.where(mask, dec, 0.0)
    with jax.named_scope(COUNTERS):
        if is_ternary:
            nnz = jnp.sum(jnp.abs(
                jnp.where(mask, values, jnp.zeros((), values.dtype))).astype(jnp.float32))
        else:
            nnz = jnp.sum((dec != 0.0).astype(jnp.float32))
    return dec, nnz


@scoped(EXCHANGE)
def decoded_exchange(values: jnp.ndarray, scale, mask, axes: Sequence[str],
                     *, is_ternary: bool):
    """The ``decoded`` wire mode, shared verbatim by both train modes: decode
    one worker's message locally (values * scale), zero non-participants, and
    fp32-psum over the worker axes. Returns ``(float sum, this worker's
    masked nnz)``. One definition keeps the cross-mode bitwise pin
    (check_wires.py) from depending on two hand-synchronized copies."""
    dec, nnz = decoded_message(values, scale, mask, is_ternary=is_ternary)
    return jax.lax.psum(dec, tuple(axes)), nnz


@scoped(EXCHANGE)
def decoded_exchange_bucket(payload: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """Bucketed ``decoded``-mode exchange: ONE fp32 psum of a whole bucket of
    pre-decoded, pre-masked messages (``decoded_message`` per leaf, assembled
    by ``dist.bucketing``). psum is element-wise per coordinate, so each
    leaf's slice of the result is bitwise the per-leaf ``decoded_exchange``
    sum; the caller splits with ``bucketing.split_bucket``."""
    return jax.lax.psum(payload, tuple(axes))


def decoded_wire_bytes(n_coords: int, n_workers: int) -> float:
    """Per-device byte ledger of the decoded fp32 psum (the float wire the
    ``decoded`` mode rides, outside any VoteWire): one ring all-reduce of
    4 B/coord."""
    return 2.0 * (n_workers - 1) / n_workers * 4.0 * n_coords


def allreduce_scalar_bytes(n_workers: int) -> float:
    """Ring all-reduce of one f32 scalar — the magnitude-sharing pmax
    (``worker_shared_linf``) and any shared-scale protocol scalar."""
    return 2.0 * (n_workers - 1) / n_workers * 4.0


def uplink_ledger(mode: str, wire: "VoteWire", n_coords: int, *,
                  share_linf: bool = False) -> float:
    """Per-device uplink bytes to exchange one n-coordinate leaf under a wire
    mode (``engine.wire_mode``: votes | scaled_votes | pack8 | decoded) — THE
    ledger definition, shared by both train steps and pinned against the
    traced collective census by ``repro.analysis`` (jaxpr + HLO passes).

    Terms: the mode's array payload (the wire's own ``wire_bytes``, or the
    decoded fp32 psum which bypasses the wire object), plus the pack8 wire's
    per-worker decode-scale gather, plus — when the compressor's scale
    protocol shares a magnitude (``engine.needs_shared_linf``) — one f32
    scalar all-reduce for the pmax'd L-inf. The shared-linf term is billed at
    the all-reduce model regardless of which wire carries the payload (the
    pmax rides the fabric, not the gather)."""
    if mode == "decoded":
        total = decoded_wire_bytes(n_coords, wire.n_workers)
    else:
        total = wire.wire_bytes(n_coords)
    if mode == "pack8":
        # per-worker decode scales ride the gather — once per ring chunk
        # (the chunked ring re-ships the scale alongside every chunk); under
        # elastic participation the worker's weight rides the same slot
        # (scalar_bytes widens to 8 B — the weight premultiplies the decode
        # scale AND ships raw for the participation total)
        total += wire.scalar_bytes() * wire.ring_chunks(n_coords)
    # elastic weight side-channel on the ternary gather wires: one f32 weight
    # per worker rides every gather (re-shipped per ring chunk, like pack8's
    # scales); the psum wires instead bill the participation payload inside
    # wire_bytes (a second per-coordinate f32 all-reduce). The decoded mode
    # bypasses the wire object entirely (weights premultiply the decode scale
    # before the f32 psum), so no side channel is traced or billed there.
    if mode != "decoded":
        total += wire.weight_bytes() * wire.ring_chunks(n_coords)
    if share_linf:
        total += allreduce_scalar_bytes(wire.n_workers)
    return total


def uplink_ledger_bucket(mode: str, wire: "VoteWire", n_coords: int,
                         n_slots: int, *, rows: Optional[int] = None,
                         ring_chunks: int = 1) -> Tuple[float, float]:
    """Per-device uplink bytes for ONE bucketed exchange carrying ``n_slots``
    leaves in ``n_coords`` padded coordinates — the bucketed variant of
    ``uplink_ledger``, split census-style into (payload, scalar) bytes.

    The payload term is the wire's bucket byte model: for the fixed-rate
    formats it is ``wire_bytes`` evaluated at the bucket's padded coordinate
    count (``n_coords`` is a whole number of canonical rows, so the packed
    ledgers are exact — padding is billed once per bucket); the
    variable-length golomb wire instead bills its payload ROWS directly
    (``rows``, the bucket's row count — slot rows are plan-time capacity,
    not coordinate rows, so a coordinate-count model would be fiction).
    The pack8 wire additionally gathers one f32 decode scale per SLOT in a
    single (n_slots,) vector all-gather next to the payload; with >= 2 slots
    that vector is array payload under the census's classification, with one
    slot it is scalar protocol traffic — the split mirrors the census's
    ``in_elems >= 2`` rule so the exact pin holds either way. The shared-linf
    term is per exchange *group*, not per bucket — ``bucketing.plan_ledger``
    bills it. ``ring_chunks`` (``wire.bucket_ring_chunks``) multiplies the
    pack8 scale-vector term: the chunked ring re-ships the whole vector
    alongside every chunk."""
    if mode == "decoded":
        payload = decoded_wire_bytes(n_coords, wire.n_workers)
    else:
        payload = wire.bucket_payload_bytes(n_coords, rows=rows)
    scalar = 0.0
    if mode == "pack8":
        # elastic participation appends ONE weight entry to the per-slot
        # scale vector (the side channel becomes (n_slots + 1,)) — the
        # census's >= 2-element payload classification follows the widened
        # vector, so the split must too
        n_side = n_slots + (1 if wire.participation is not None else 0)
        scales = float((wire.n_workers - 1) * 4 * n_side) * int(ring_chunks)
        if n_side >= 2:
            payload += scales
        else:
            scalar += scales
    elif mode != "decoded":
        # ternary gather wires under elastic participation gather a (1,) f32
        # weight per worker next to the bucket (re-shipped per ring chunk);
        # one element -> scalar protocol traffic under the census split. The
        # decoded mode's bucket psum bypasses the wire (no side channel).
        scalar += wire.weight_bytes() * int(ring_chunks)
    return payload, scalar


def vote_allgather_packed8(payload: jnp.ndarray, scale, axes: Sequence[str],
                           size: int, shape, *,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """All-gather of int8 sign*level payloads + per-worker f32 scales, fused
    dequantize-sum — the pack8 (8-bit QSGD) wire exchange.

    Wire bytes = M * (ceil'd d + 4) per device; returns the float32 decoded
    sum ``sum_m scale_m * levels_m`` of shape ``shape`` — exactly what the
    mean server consumes. Workers are accumulated strictly in worker-index
    order (the gather order), which is also how the decoded-psum wire
    associates its float adds, so the two wires agree bitwise.

    ``backend='jnp'`` skips the gather entirely: each worker dequantizes its
    own payload and the sum IS a float psum — the reference program whose
    association the kernel path reproduces. Same values, fp32 fabric bytes;
    the kernel backends run the honest 1 B/coord gather.
    """
    from repro.kernels import common as kcommon

    scale = jnp.asarray(scale, jnp.float32)
    if backend == "jnp":
        dec = kcommon.from_2d(payload, size, shape).astype(jnp.float32) * scale
        return jax.lax.psum(dec, tuple(axes))
    gathered = jax.lax.all_gather(payload, tuple(axes), axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, tuple(axes), axis=0, tiled=False)
    interpret = (backend == "interpret") if backend is not None else None
    return _unpack8_op()(gathered, scales, size, shape, interpret=interpret)


# ---------------------------------------------------------------------------
# Ring-pipelined gather: ppermute chunks with streaming decode-sum
# ---------------------------------------------------------------------------

#: Default ring chunk size (canonical payload rows per chunk) when a caller
#: asks for ring mode without a size: 256 rows is a 32 KiB pack2 / 128 KiB
#: pack8 chunk — big enough to amortize a ppermute launch on the host
#: backends, small enough that two in-flight chunks stay far under one
#: monolithic gather. TPU latency tuning of this knob is deferred to the
#: hardware pass (see ROADMAP); this is the documented CPU-container default.
DEFAULT_RING_CHUNK_ROWS = 256


def ring_perm(m: int) -> list:
    """The M-cycle permutation (i -> i+1 mod M): after one application every
    worker holds its predecessor's buffer, so M-1 hops visit every peer.
    ``m == 1`` degenerates to the identity [(0, 0)] — trace-legal, and the
    hop loop's condition is already false there."""
    return [(i, (i + 1) % m) for i in range(m)]


def ring_permute(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """Sanctioned one-hop ring shift over the (flattened) worker axes: the
    ONLY ppermute call site in the repo (raw ``lax.ppermute`` outside this
    module is a repolint error). Row-major flat product indexing over
    ``axes`` — the same worker order as ``worker_index`` and the gather
    wires' axis-0 stacking, so ring arrival order is a pure rotation of the
    monolithic gather's worker order."""
    axes = tuple(axes)
    return jax.lax.ppermute(x, axes, ring_perm(worker_count(axes)))


def _ring_chunk_spans(total_rows: int, chunk_rows: Optional[int]) -> tuple:
    """Static (row_start, rows) chunk framing of a payload: greedy
    ``chunk_rows``-row spans with a short tail. ``None`` = one whole-payload
    chunk (a chunked ring degenerates to an unchunked one, which is how the
    ledger treats a monolithic gather's chunk count too)."""
    if chunk_rows is None or total_rows <= chunk_rows:
        return ((0, total_rows),)
    spans = []
    r = 0
    while r < total_rows:
        spans.append((r, min(int(chunk_rows), total_rows - r)))
        r += spans[-1][1]
    return tuple(spans)


def _slot_groups(slots, chunk_rows: Optional[int]) -> tuple:
    """Golomb chunk framing: greedy groups of CONSECUTIVE WHOLE slots whose
    rows fit in ``chunk_rows``. The coded stream is not row-addressable mid-
    slot (each slot is one self-describing capacity stream), so golomb
    chunks on slot boundaries; a slot bigger than ``chunk_rows`` rides the
    ring alone as an oversized chunk."""
    slots = tuple(slots)
    if chunk_rows is None:
        return (slots,)
    groups, cur, cur_rows = [], [], 0
    for s in slots:
        if cur and cur_rows + s.rows > chunk_rows:
            groups.append(tuple(cur))
            cur, cur_rows = [], 0
        cur.append(s)
        cur_rows += s.rows
    if cur:
        groups.append(tuple(cur))
    return tuple(groups)


def _chunk_segments(slots, r0: int, nr: int) -> tuple:
    """Which slot row-ranges a [r0, r0+nr) chunk carries: static
    (slot_index, slot, seg_row_start, seg_rows) tuples, in row order. Used
    by the pack8 bucket ring — its slots are sublane-aligned, so every
    segment boundary is a valid kernel tile boundary when the chunk size
    is a sublane multiple."""
    segs = []
    for i, s in enumerate(slots):
        a = max(r0, s.row_start)
        b = min(r0 + nr, s.row_start + s.rows)
        if b > a:
            segs.append((i, s, a, b - a))
    return tuple(segs)


def _ring_accumulate(payload: jnp.ndarray, side: tuple, decode_fn,
                     axes: Tuple[str, ...], m: int):
    """One chunk's M-1-hop ring exchange with streaming decode-sum.

    Decode our own chunk first, then ``lax.while_loop`` the ring: each hop
    shifts the payload (and any side-channel arrays, e.g. pack8 decode
    scales) one worker forward and adds ``decode_fn``'s decode of the
    arriving slice into the accumulator — the gathered ``(M, ...)`` tensor
    never exists; peak HBM is the in-flight chunk plus the accumulator.
    ``decode_fn(chunk, *side)`` may return an array or a tuple of arrays
    (per-slot sums); accumulation is tree-mapped. The hop loop is a
    ``while_loop`` (never a scan) on purpose: the census walker descends
    its body with trips=1, so the single traced ppermute per chunk bills as
    one (M-1)-hop ring launch regardless of the build-time mesh size — at
    M=1 the loop body never runs and the decode of our own chunk is the
    whole sum."""
    acc = decode_fn(payload, *side)

    def cond(carry):
        return carry[0] < m

    def body(carry):
        k, b, sd, a = carry
        b = ring_permute(b, axes)
        sd = tuple(ring_permute(s, axes) for s in sd)
        a = jax.tree_util.tree_map(jnp.add, a, decode_fn(b, *sd))
        return (k + 1, b, sd, a)

    _, _, _, acc = jax.lax.while_loop(
        cond, body, (jnp.int32(1), payload, tuple(side), acc))
    return acc


# ---------------------------------------------------------------------------
# The wire abstraction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VoteWire:
    """One vote-exchange wire: message format + collective + byte ledger.

    Static (python-level) object closed over by the jitted train step; built
    once per step via ``make_vote_wire``. ``exchange`` must run inside the
    worker-axes shard_map. All wires return the same vote totals bitwise —
    only the message format and the bytes on the fabric differ.

    With a ``participation`` spec attached (``make_vote_wire(...,
    participation=...)``), the elastic exchange family
    (``exchange_weighted`` / ``exchange_bucket_weighted``) is live: the wire
    carries each worker's effective weight (static per-worker weight x
    dynamic report mask) next to the payload — as a second per-coordinate
    f32 all-reduce on the psum wires, as a billed (1,)-per-worker gather
    side channel on the ternary gather wires, folded into the existing
    decode-scale channel (widened to carry the raw weight too) on pack8 —
    and returns ``(sum_m w_m * votes_m, W = sum_reporting w_m)`` for the
    participation-normalized server deadband.
    """

    axes: Tuple[str, ...]
    n_workers: int
    participation: Optional[ParticipationSpec] = None

    name = "psum"
    #: native uplink message format ("int8": leaf-shaped int8 ternary votes,
    #: "pack2": 2-bit packed uint8 canonical view, "pack8": int8 sign*level
    #: canonical view); ``engine.compress_leaf(wire=...)`` emits it and
    #: validates it against the CompressorSpec's declared wire_format
    native_format = "int8"

    @property
    def wants_packed(self) -> bool:
        """Does this wire speak a packed canonical view (vs leaf-shaped votes)?"""
        return self.native_format != "int8"

    @property
    def gathers_packed(self) -> bool:
        """Is the exchange one all-gather of whole 2-bit payloads (``gather``),
        so the server can decode them inside its update? False for the psum
        wires, the other payload formats and the chunked ring, which
        accumulates the decoded sum hop by hop."""
        return False

    @scoped(UPLINK)
    def mask_message(self, values: jnp.ndarray, mask) -> jnp.ndarray:
        """Zero a non-participating worker's message, in wire-native format
        (an all-zero packed byte decodes to four zero votes)."""
        return jnp.where(mask, values, jnp.zeros((), values.dtype))

    @scoped(COUNTERS)
    def message_nnz(self, values: jnp.ndarray) -> jnp.ndarray:
        """Number of nonzero votes in one wire-native message (f32 scalar)."""
        return jnp.sum(jnp.abs(values).astype(jnp.float32))

    @scoped(EXCHANGE)
    def exchange(self, values: jnp.ndarray, size: int, shape, *,
                 scale=None) -> jnp.ndarray:
        """Wire-native message -> integer vote sum of shape ``shape``.

        ``scale`` is only meaningful on the pack8 wire (each worker's decode
        scale rides the gather); the integer vote wires reject it loudly —
        a shared scale stays OUTSIDE the exchange (``scaled_votes`` decode)."""
        if scale is not None:
            raise ValueError(
                f"the {self.name!r} vote wire exchanges raw integer votes; "
                f"a decode scale inside the exchange is a pack8-wire concept")
        return vote_psum(values, self.axes, self.n_workers)

    def _require_participation(self):
        if self.participation is None:
            raise ValueError(
                f"the {self.name!r} wire was built without a "
                f"ParticipationSpec; the weighted exchange family is the "
                f"elastic-participation path — pass participation= to "
                f"make_vote_wire")

    @scoped(EXCHANGE)
    def exchange_weighted(self, values: jnp.ndarray, size: int, shape, *,
                          weight, scale=None):
        """Elastic exchange: ``(sum_m w_m * votes_m, per-coordinate
        participation total)``. ``weight`` is THIS worker's effective f32
        weight (static weight x report mask — exactly 0.0 when not
        reporting; ``values`` must already be masked to zeros). The psum
        wires all-reduce two f32 arrays — the weighted vote and the realized
        participation count per coordinate — both billed as payload."""
        self._require_participation()
        if scale is not None:
            raise ValueError(
                f"the {self.name!r} vote wire exchanges raw integer votes; "
                f"a decode scale inside the exchange is a pack8-wire concept")
        w = jnp.asarray(weight, jnp.float32)
        wv = jax.lax.psum(values.astype(jnp.float32) * w, tuple(self.axes))
        wtot = jax.lax.psum(jnp.broadcast_to(w, shape).astype(jnp.float32),
                            tuple(self.axes))
        return wv, wtot

    @scoped(EXCHANGE)
    def exchange_bucket(self, payload: jnp.ndarray, bucket, *, scale=None):
        """One bucket of wire-native messages -> per-leaf aggregates, ONE
        collective. ``payload`` is the assembled (rows, width) buffer
        (``dist.bucketing.assemble_bucket``), ``bucket`` its static
        ``bucketing.Bucket`` layout; returns a list of per-leaf sums in the
        leaves' shapes, aligned with ``bucket.slots``. The exchange is
        element-wise per coordinate, so every slice is bitwise the per-leaf
        ``exchange`` of the same message — the cross-granularity pin
        (tests/mdev) rides on that. ``scale`` is pack8-only, as in
        ``exchange``."""
        if scale is not None:
            raise ValueError(
                f"the {self.name!r} vote wire exchanges raw integer votes; "
                f"a decode scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        return bucketing.split_bucket(
            vote_psum(payload, self.axes, self.n_workers), bucket)

    @scoped(EXCHANGE)
    def exchange_bucket_weighted(self, payload: jnp.ndarray, bucket, *,
                                 weight, scale=None):
        """Bucketed elastic exchange: per-leaf ``(weighted vote sums,
        participation total)`` for one assembled bucket — ``(parts, wtot)``
        where ``parts`` aligns with ``bucket.slots`` and ``wtot`` is the
        realized participation (per-coordinate f32 arrays per slot on the
        psum wires, one scalar on the gather wires — per-worker weights are
        per-message, so every coordinate shares it)."""
        self._require_participation()
        if scale is not None:
            raise ValueError(
                f"the {self.name!r} vote wire exchanges raw integer votes; "
                f"a decode scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        w = jnp.asarray(weight, jnp.float32)
        wv = jax.lax.psum(payload.astype(jnp.float32) * w, tuple(self.axes))
        wtot = jax.lax.psum(
            jnp.broadcast_to(w, payload.shape).astype(jnp.float32),
            tuple(self.axes))
        return (bucketing.split_bucket(wv, bucket),
                bucketing.split_bucket(wtot, bucket))

    def wire_bytes(self, n_coords: int) -> float:
        """Per-device wire bytes to exchange one n-coordinate leaf's votes
        (ring-collective first principles, real payload sizes). Under elastic
        participation the psum wires exchange TWO f32 arrays (weighted vote +
        per-coordinate participation count) instead of one narrow integer
        payload — billed honestly."""
        m = self.n_workers
        if self.participation is not None:
            return 2.0 * decoded_wire_bytes(n_coords, m)
        payload = n_coords * jnp.dtype(_sum_dtype(m)).itemsize
        return 2.0 * (m - 1) / m * payload

    def scalar_bytes(self) -> float:
        """Ledger for the f32 decode scale(s) riding alongside a leaf's
        payload: one ring all-reduce of 4 bytes (the magnitude-shared scale of
        ``worker_shared_linf``). The pack8 wire overrides this with its
        per-worker scale gather."""
        m = self.n_workers
        return 2.0 * (m - 1) / m * 4.0

    def weight_bytes(self) -> float:
        """Elastic weight side-channel ledger: bytes to ship this worker's
        f32 effective weight alongside ONE payload exchange (multiplied by
        the ring chunk count upstream — the chunked ring re-ships it). Zero
        for the psum wires (their participation payload bills inside
        ``wire_bytes``) and for pack8 (the weight widens ``scalar_bytes``);
        the ternary gather wires override with the (M-1)-peer gather."""
        return 0.0

    def bucket_payload_bytes(self, n_coords: int,
                             rows: Optional[int] = None) -> float:
        """Payload ledger for ONE bucket of this wire: the fixed-rate wires
        bill by padded coordinate count (rows carry LANES coordinates each,
        so ``wire_bytes(n_coords)`` is exact); the variable-length golomb
        wire overrides this to bill its capacity rows directly."""
        return self.wire_bytes(n_coords)

    def ring_chunks(self, n_coords: int) -> int:
        """Number of ring chunks (= payload collective launches) to exchange
        one n-coordinate leaf. 1 for the psum wires and for unchunked
        gathers; the gather wires override with their chunk framing."""
        return 1

    def bucket_ring_chunks(self, bucket) -> int:
        """Ring chunk count for ONE bucket exchange (cf. ``ring_chunks``)."""
        return 1

    def gather_hbm_bytes(self, n_coords: int) -> float:
        """Peak HBM footprint of the gathered payload while exchanging one
        n-coordinate leaf: M x payload for a monolithic gather, ~2 chunks
        (in-flight + decoding) for the ring, 0 for the psum wires (a fabric
        reduction never materializes a gathered tensor). A residency model,
        not wire traffic — total fabric bytes (``wire_bytes``) are identical
        either way."""
        return 0.0

    def bucket_gather_hbm_bytes(self, bucket) -> float:
        """Peak gathered-payload HBM for ONE bucket exchange (cf.
        ``gather_hbm_bytes``)."""
        return 0.0


@dataclasses.dataclass(frozen=True)
class HierVoteWire(VoteWire):
    """Two-level psum: narrow within axes[1] (intra-pod), widened across
    axes[0] (DCN hop). Requires exactly two worker axes."""

    inner_size: int = 1
    outer_size: int = 1

    name = "hier"

    @scoped(EXCHANGE)
    def exchange(self, values, size, shape, *, scale=None):
        if scale is not None:
            raise ValueError(
                "the 'hier' vote wire exchanges raw integer votes; a decode "
                "scale inside the exchange is a pack8-wire concept")
        return vote_psum_hier(values, self.axes[1], self.axes[0],
                              self.inner_size, self.outer_size)

    @scoped(EXCHANGE)
    def exchange_bucket(self, payload, bucket, *, scale=None):
        if scale is not None:
            raise ValueError(
                "the 'hier' vote wire exchanges raw integer votes; a decode "
                "scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        return bucketing.split_bucket(
            vote_psum_hier(payload, self.axes[1], self.axes[0],
                           self.inner_size, self.outer_size), bucket)

    def _hier_f32_psum(self, x: jnp.ndarray) -> jnp.ndarray:
        # elastic sums are f32, so there is no narrow/widen dtype split —
        # but the exchange stays two-level to keep the hierarchical wire
        # shape (intra-pod reduce, then the DCN hop)
        return jax.lax.psum(jax.lax.psum(x, self.axes[1]), self.axes[0])

    @scoped(EXCHANGE)
    def exchange_weighted(self, values, size, shape, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the 'hier' vote wire exchanges raw integer votes; a decode "
                "scale inside the exchange is a pack8-wire concept")
        w = jnp.asarray(weight, jnp.float32)
        wv = self._hier_f32_psum(values.astype(jnp.float32) * w)
        wtot = self._hier_f32_psum(
            jnp.broadcast_to(w, shape).astype(jnp.float32))
        return wv, wtot

    @scoped(EXCHANGE)
    def exchange_bucket_weighted(self, payload, bucket, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the 'hier' vote wire exchanges raw integer votes; a decode "
                "scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        w = jnp.asarray(weight, jnp.float32)
        wv = self._hier_f32_psum(payload.astype(jnp.float32) * w)
        wtot = self._hier_f32_psum(
            jnp.broadcast_to(w, payload.shape).astype(jnp.float32))
        return (bucketing.split_bucket(wv, bucket),
                bucketing.split_bucket(wtot, bucket))

    def wire_bytes(self, n_coords):
        # both ring terms share one (symmetric) formula — make_vote_wire
        # validates the axis sizes >= 1 at build time, so neither denominator
        # needs a zero guard
        ni, no = self.inner_size, self.outer_size
        if self.participation is not None:
            # two f32 arrays (weighted vote + participation count), both
            # levels at 4 B/coord — no narrow inner dtype to exploit
            inner = 2.0 * (ni - 1) / ni * 4.0 * n_coords
            outer = 2.0 * (no - 1) / no * 4.0 * n_coords
            return 2.0 * (inner + outer)
        inner = 2.0 * (ni - 1) / ni * n_coords * jnp.dtype(_sum_dtype(ni)).itemsize
        outer = 2.0 * (no - 1) / no * n_coords * jnp.dtype(_sum_dtype(ni * no)).itemsize
        return inner + outer


@dataclasses.dataclass(frozen=True)
class PackedVoteWire(VoteWire):
    """All-gather of the 2-bit packed wire + fused decode-sum. The message IS
    the packed canonical view — produced in one pass by the fused
    sparsign_pack2bit kernel on the kernel backends. With ``ring_chunk_rows``
    set, the gather becomes the chunked ppermute ring (module docstring):
    int32 accumulation, bitwise the monolithic gather."""

    backend: Optional[str] = None
    ring_chunk_rows: Optional[int] = None

    name = "allgather_packed"
    native_format = "pack2"

    @scoped(COUNTERS)
    def message_nnz(self, values):
        # count nonzero 2-bit codes straight off the bytes: codes are {0,1,2},
        # so (b | b>>1) has bit 0 of each code set iff the code is nonzero
        nz = (values | (values >> 1)) & jnp.uint8(0x55)
        cnt = ((nz & 1) + ((nz >> 2) & 1) + ((nz >> 4) & 1) + ((nz >> 6) & 1))
        return jnp.sum(cnt.astype(jnp.float32))

    def _ring_decode_flat(self, payload: jnp.ndarray) -> jnp.ndarray:
        """Ring-exchange a (rows, LANES//4) packed payload in row chunks,
        returning the flat (rows*LANES,) int32 vote sum. Every span is a
        sublane multiple (canonical rows are sublane-padded and the chunk
        size is validated as one), so each chunk decodes through the
        unmodified fused kernel as a self-contained pack2 stream."""
        from repro.kernels import common as kcommon
        parts = []
        for r0, nr in _ring_chunk_spans(payload.shape[0], self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)

            def decode(b, _nr=nr):
                return _packed_decode_sum(b[None], _nr * kcommon.LANES,
                                          (_nr * kcommon.LANES,),
                                          backend=self.backend)

            parts.append(_ring_accumulate(chunk, (), decode, self.axes,
                                          self.n_workers))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    @property
    def gathers_packed(self) -> bool:
        return self.ring_chunk_rows is None

    @scoped(EXCHANGE)
    def gather(self, values) -> jnp.ndarray:
        """The exchange without its decode: every worker's (rows, LANES//4)
        packed message, all-gathered to (M, rows, LANES//4) — what
        ``engine.server_apply_packed`` decodes inside the update."""
        return jax.lax.all_gather(values, self.axes, axis=0, tiled=False)

    @scoped(EXCHANGE)
    def exchange(self, values, size, shape, *, scale=None):
        if scale is not None:
            raise ValueError(
                "the 2-bit packed vote wire exchanges raw ternary votes; a "
                "decode scale inside the exchange is a pack8-wire concept")
        if self.ring_chunk_rows is not None:
            flat = self._ring_decode_flat(values)
            total = jax.lax.slice(flat, (0,), (size,)).reshape(shape)
            return total.astype(_sum_dtype(self.n_workers))
        gathered = self.gather(values)
        total = _packed_decode_sum(gathered, size, shape, backend=self.backend)
        return total.astype(_sum_dtype(self.n_workers))

    @scoped(EXCHANGE)
    def exchange_bucket(self, payload, bucket, *, scale=None):
        """ONE all-gather of the whole packed bucket + one fused decode-sum
        over it, then split on the decoded stream. pack2 packs each canonical
        row independently, so the bucket (a row-concatenation of per-leaf
        payloads) is itself a valid pack2 stream and the whole-bucket decode
        is bitwise the per-leaf decode at every coordinate — which is also
        what lets the ring path chunk the bucket on ANY sublane-aligned row
        boundary, slots included."""
        if scale is not None:
            raise ValueError(
                "the 2-bit packed vote wire exchanges raw ternary votes; a "
                "decode scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        n = bucket.n_coords
        if self.ring_chunk_rows is not None:
            flat = self._ring_decode_flat(payload)
            return bucketing.split_bucket(
                flat.astype(_sum_dtype(self.n_workers)), bucket)
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        total = _packed_decode_sum(gathered, n, (n,), backend=self.backend)
        return bucketing.split_bucket(
            total.astype(_sum_dtype(self.n_workers)), bucket)

    def _ring_wdecode_flat(self, payload: jnp.ndarray, w1: jnp.ndarray):
        """Weighted ring exchange of a (rows, LANES//4) packed payload: the
        (1,) effective weight rides every chunk's ring as the side channel
        (re-shipped per chunk — the ledger's ``weight_bytes x ring_chunks``),
        each arriving slice weighted-decode-summed at M=1. Returns the flat
        f32 weighted vote sum and the realized participation total (the
        weights accumulate around the same ring)."""
        from repro.kernels import common as kcommon
        parts, wtot = [], None
        for r0, nr in _ring_chunk_spans(payload.shape[0], self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)

            def decode(b, wv, _nr=nr):
                s = _packed_decode_wsum(b[None], wv, _nr * kcommon.LANES,
                                        (_nr * kcommon.LANES,),
                                        backend=self.backend)
                return (s, jnp.sum(wv))

            acc, wt = _ring_accumulate(chunk, (w1,), decode, self.axes,
                                       self.n_workers)
            parts.append(acc)
            wtot = wt if wtot is None else wtot
        return (parts[0] if len(parts) == 1 else jnp.concatenate(parts)), wtot

    @scoped(EXCHANGE)
    def exchange_weighted(self, values, size, shape, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the 2-bit packed vote wire exchanges raw ternary votes; a "
                "decode scale inside the exchange is a pack8-wire concept")
        w1 = jnp.asarray(weight, jnp.float32).reshape((1,))
        if self.ring_chunk_rows is not None:
            flat, wtot = self._ring_wdecode_flat(values, w1)
            return jax.lax.slice(flat, (0,), (size,)).reshape(shape), wtot
        gathered = jax.lax.all_gather(values, self.axes, axis=0, tiled=False)
        wvec = jax.lax.all_gather(w1, self.axes, axis=0,
                                  tiled=False).reshape(-1)
        wv = _packed_decode_wsum(gathered, wvec, size, shape,
                                 backend=self.backend)
        return wv, jnp.sum(wvec)

    @scoped(EXCHANGE)
    def exchange_bucket_weighted(self, payload, bucket, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the 2-bit packed vote wire exchanges raw ternary votes; a "
                "decode scale inside the exchange is a pack8-wire concept")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        w1 = jnp.asarray(weight, jnp.float32).reshape((1,))
        n = bucket.n_coords
        if self.ring_chunk_rows is not None:
            flat, wtot = self._ring_wdecode_flat(payload, w1)
            return bucketing.split_bucket(flat, bucket), wtot
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        wvec = jax.lax.all_gather(w1, self.axes, axis=0,
                                  tiled=False).reshape(-1)
        total = _packed_decode_wsum(gathered, wvec, n, (n,),
                                    backend=self.backend)
        return bucketing.split_bucket(total, bucket), jnp.sum(wvec)

    def weight_bytes(self):
        # the (1,) f32 effective weight gathered from M-1 peers next to the
        # packed payload — the elastic side channel
        if self.participation is None:
            return 0.0
        return float((self.n_workers - 1) * 4.0)

    def wire_bytes(self, n_coords):
        # ring all-gather: each device transmits its (padded) packed payload
        # to M-1 peers — no reduction on the fabric. The chunked ppermute
        # ring ships the same bytes (every chunk visits every worker), so
        # one formula serves both exchanges.
        return float((self.n_workers - 1) * packed_nbytes(n_coords))

    def ring_chunks(self, n_coords):
        from repro.kernels import common as kcommon
        return len(_ring_chunk_spans(kcommon.canonical_rows(n_coords),
                                     self.ring_chunk_rows))

    def bucket_ring_chunks(self, bucket):
        return len(_ring_chunk_spans(bucket.rows, self.ring_chunk_rows))

    def _gather_hbm(self, rows: int) -> float:
        from repro.kernels import common as kcommon
        row_bytes = kcommon.LANES // 4
        if self.ring_chunk_rows is None:
            return float(self.n_workers * rows * row_bytes)
        max_nr = max(nr for _, nr in _ring_chunk_spans(rows, self.ring_chunk_rows))
        return float(2 * max_nr * row_bytes)

    def gather_hbm_bytes(self, n_coords):
        from repro.kernels import common as kcommon
        return self._gather_hbm(kcommon.canonical_rows(n_coords))

    def bucket_gather_hbm_bytes(self, bucket):
        return self._gather_hbm(bucket.rows)


@dataclasses.dataclass(frozen=True)
class Pack8Wire(VoteWire):
    """All-gather of int8 sign*level payloads (the pack8 wire format) + fused
    dequantize-sum — the non-ternary 8-bit twin of ``PackedVoteWire``. The
    message IS the canonical (rows, LANES) int8 view of the signed levels,
    produced in one pass by the fused qsgd8_pack8 kernel on the kernel
    backends; each worker's f32 decode scale rides the gather next to it and
    the exchange returns the float32 decoded sum the mean server consumes.

    With ``ring_chunk_rows`` set, the kernel backends ring the payload in
    sublane-tile chunks with the decode scales riding the same ring as an
    f32 side channel; f32 sums then associate in ring-arrival order — a
    different (deterministic) association than the worker-order oracle,
    allclose but not bitwise. The jnp backend keeps its psum-oracle program
    regardless (there is no gather to ring); the byte/HBM ledgers model the
    honest gather wire either way, exactly as ``wire_bytes`` already does."""

    backend: Optional[str] = None
    ring_chunk_rows: Optional[int] = None

    name = "allgather_packed8"
    native_format = "pack8"

    @scoped(COUNTERS)
    def message_nnz(self, values):
        # nonzero LEVELS, not their magnitudes: |level| would overweight
        # large coordinates in the nnz_frac metric
        return jnp.sum((values != 0).astype(jnp.float32))

    def _interpret(self):
        return (self.backend == "interpret") if self.backend is not None else None

    @scoped(EXCHANGE)
    def exchange(self, values, size, shape, *, scale=None):
        if scale is None:
            raise ValueError(
                "the pack8 wire dequantizes during the exchange and needs "
                "this worker's decode scale (CompressedGrad.scale)")
        if self.ring_chunk_rows is not None and self.backend != "jnp":
            return self._ring_exchange(values, scale, size, shape)
        return vote_allgather_packed8(values, scale, self.axes, size, shape,
                                      backend=self.backend)

    def _ring_exchange(self, payload, scale, size, shape):
        """Chunked ring exchange of one leaf: the (1,) decode scale rides
        every chunk's ring next to the payload (re-shipped per chunk — the
        ledger's ``ring_chunks`` factor), each arriving slice dequantize-
        summed through the fused kernel at M=1."""
        from repro.kernels import common as kcommon
        sc = jnp.asarray(scale, jnp.float32).reshape((1,))
        parts = []
        for r0, nr in _ring_chunk_spans(payload.shape[0], self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)

            def decode(b, s, _nr=nr):
                return _unpack8_op()(b[None], s, _nr * kcommon.LANES,
                                     (_nr * kcommon.LANES,),
                                     interpret=self._interpret())

            parts.append(_ring_accumulate(chunk, (sc,), decode, self.axes,
                                          self.n_workers))
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return jax.lax.slice(flat, (0,), (size,)).reshape(shape)

    @scoped(EXCHANGE)
    def exchange_bucket(self, payload, bucket, *, scale=None):
        """ONE payload all-gather + ONE (n_slots,) scale-vector all-gather for
        the whole bucket. Slots are sublane-aligned (``bucketing``'s pack8
        ``align_rows``), so each leaf's gathered row slice IS its per-leaf
        canonical view and decodes through the unmodified fused
        ``unpack8_sum`` kernel with that slot's per-worker scales — worker
        accumulation order and rounding points are bitwise the per-leaf wire.
        ``scale`` is the (n_slots,) f32 vector of the slots' decode scales."""
        if scale is None:
            raise ValueError(
                "the pack8 wire dequantizes during the exchange and needs "
                "the bucket's per-slot decode scales (one f32 per leaf)")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        scale = jnp.asarray(scale, jnp.float32).reshape(-1)
        assert scale.shape[0] == len(bucket.slots), (scale.shape, bucket)
        if self.backend == "jnp":
            # the psum oracle program, as in vote_allgather_packed8: decode
            # our own payload (per-row slot scales), ONE fp32 psum, split
            row_scales = jnp.concatenate(
                [jnp.broadcast_to(scale[i], (s.rows,))
                 for i, s in enumerate(bucket.slots)]
                + ([jnp.zeros((bucket.rows - sum(s.rows for s in bucket.slots),),
                              jnp.float32)] if bucket.rows > sum(
                                  s.rows for s in bucket.slots) else []))
            dec = payload.astype(jnp.float32) * row_scales[:, None]
            return bucketing.split_bucket(jax.lax.psum(dec, self.axes), bucket)
        if self.ring_chunk_rows is not None:
            return self._ring_exchange_bucket(payload, scale, bucket)
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        scales = jax.lax.all_gather(scale, self.axes, axis=0, tiled=False)
        interpret = self._interpret()
        out = []
        for i, s in enumerate(bucket.slots):
            rows = jax.lax.slice_in_dim(gathered, s.row_start,
                                        s.row_start + s.rows, axis=1)
            out.append(_unpack8_op()(rows, scales[:, i], s.size, s.shape,
                                     interpret=interpret))
        return out

    def _ring_exchange_bucket(self, payload, scale, bucket):
        """Chunked ring exchange of one bucket: payload chunks on sublane
        row tiles, the whole (n_slots,) scale vector riding every chunk's
        ring. Slots are sublane-aligned (``bucketing``'s pack8
        ``align_rows``), so every chunk/slot intersection is a tile-aligned
        segment decoding through the unmodified fused kernel; per-slot
        segments re-concatenate in row order."""
        from repro.kernels import common as kcommon
        outs = [[] for _ in bucket.slots]
        for r0, nr in _ring_chunk_spans(bucket.rows, self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)
            segs = _chunk_segments(bucket.slots, r0, nr)

            def decode(b, sc, _segs=segs, _r0=r0):
                res = []
                for i, _s, a, srows in _segs:
                    rows = jax.lax.slice_in_dim(b, a - _r0, a - _r0 + srows,
                                                axis=0)
                    res.append(_unpack8_op()(
                        rows[None], sc[i:i + 1], srows * kcommon.LANES,
                        (srows * kcommon.LANES,), interpret=self._interpret()))
                return tuple(res)

            part = _ring_accumulate(chunk, (scale,), decode, self.axes,
                                    self.n_workers)
            for (i, _s, _a, _srows), arr in zip(segs, part):
                outs[i].append(arr)
        result = []
        for s, parts in zip(bucket.slots, outs):
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            result.append(jax.lax.slice(flat, (0,), (s.size,)).reshape(s.shape))
        return result

    @scoped(EXCHANGE)
    def exchange_weighted(self, values, size, shape, *, weight, scale=None):
        """Elastic pack8 exchange: the effective weight PREMULTIPLIES the
        decode scale (a dropped worker's scale*0 zeroes its dequantized
        contribution — the fused kernel is unchanged) and also ships raw in
        the widened (2,) side channel ``[scale * w, w]`` so the server can
        normalize by the realized participation total."""
        self._require_participation()
        if scale is None:
            raise ValueError(
                "the pack8 wire dequantizes during the exchange and needs "
                "this worker's decode scale (CompressedGrad.scale)")
        w = jnp.asarray(weight, jnp.float32)
        sc = jnp.asarray(scale, jnp.float32).reshape(())
        if self.backend == "jnp":
            # the psum oracle program, weighted: decode with scale * w
            from repro.kernels import common as kcommon
            dec = kcommon.from_2d(values, size, shape).astype(jnp.float32) \
                * (sc * w)
            return (jax.lax.psum(dec, tuple(self.axes)),
                    scalar_psum(w, self.axes))
        side = jnp.stack([sc * w, w])
        if self.ring_chunk_rows is not None:
            return self._ring_exchange_weighted(values, side, size, shape)
        gathered = jax.lax.all_gather(values, self.axes, axis=0, tiled=False)
        sides = jax.lax.all_gather(side, self.axes, axis=0, tiled=False)
        wv = _unpack8_op()(gathered, sides[:, 0], size, shape,
                                  interpret=self._interpret())
        return wv, jnp.sum(sides[:, 1])

    def _ring_exchange_weighted(self, payload, side, size, shape):
        """Weighted chunked ring: the (2,) ``[scale * w, w]`` side channel
        rides every chunk (re-shipped per chunk — ``scalar_bytes`` widens to
        8 B under participation and ``uplink_ledger`` multiplies by
        ``ring_chunks``); the raw weights accumulate around the ring into
        the participation total."""
        from repro.kernels import common as kcommon
        op = _unpack8_op()
        parts, wtot = [], None
        for r0, nr in _ring_chunk_spans(payload.shape[0], self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)

            def decode(b, s, _nr=nr):
                val = op(b[None], s[0:1], _nr * kcommon.LANES,
                         (_nr * kcommon.LANES,), interpret=self._interpret())
                return (val, s[1])

            acc, wt = _ring_accumulate(chunk, (side,), decode, self.axes,
                                       self.n_workers)
            parts.append(acc)
            wtot = wt if wtot is None else wtot
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return jax.lax.slice(flat, (0,), (size,)).reshape(shape), wtot

    @scoped(EXCHANGE)
    def exchange_bucket_weighted(self, payload, bucket, *, weight, scale=None):
        """Bucketed elastic pack8 exchange: the per-slot scale vector is
        premultiplied by the effective weight and widened by one raw-weight
        entry — ONE (n_slots + 1,) side-channel gather for the whole
        bucket."""
        self._require_participation()
        if scale is None:
            raise ValueError(
                "the pack8 wire dequantizes during the exchange and needs "
                "the bucket's per-slot decode scales (one f32 per leaf)")
        from repro.dist import bucketing  # lazy: bucketing imports this module
        w = jnp.asarray(weight, jnp.float32)
        scale = jnp.asarray(scale, jnp.float32).reshape(-1)
        assert scale.shape[0] == len(bucket.slots), (scale.shape, bucket)
        if self.backend == "jnp":
            row_scales = jnp.concatenate(
                [jnp.broadcast_to(scale[i] * w, (s.rows,))
                 for i, s in enumerate(bucket.slots)]
                + ([jnp.zeros((bucket.rows - sum(s.rows for s in bucket.slots),),
                              jnp.float32)] if bucket.rows > sum(
                                  s.rows for s in bucket.slots) else []))
            dec = payload.astype(jnp.float32) * row_scales[:, None]
            return (bucketing.split_bucket(jax.lax.psum(dec, self.axes),
                                           bucket),
                    scalar_psum(w, self.axes))
        side = jnp.concatenate([scale * w, w.reshape((1,))])
        if self.ring_chunk_rows is not None:
            return self._ring_exchange_bucket_weighted(payload, side, bucket)
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        sides = jax.lax.all_gather(side, self.axes, axis=0, tiled=False)
        op = _unpack8_op()
        out = []
        for i, s in enumerate(bucket.slots):
            rows = jax.lax.slice_in_dim(gathered, s.row_start,
                                        s.row_start + s.rows, axis=1)
            out.append(op(rows, sides[:, i], s.size, s.shape,
                          interpret=self._interpret()))
        return out, jnp.sum(sides[:, -1])

    def _ring_exchange_bucket_weighted(self, payload, side, bucket):
        """Weighted bucket ring: the whole (n_slots + 1,) side vector rides
        every chunk; per-slot segments decode with the premultiplied scales
        and the raw-weight tail entry accumulates into the participation
        total."""
        from repro.kernels import common as kcommon
        op = _unpack8_op()
        outs = [[] for _ in bucket.slots]
        wtot = None
        for r0, nr in _ring_chunk_spans(bucket.rows, self.ring_chunk_rows):
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + nr, axis=0)
            segs = _chunk_segments(bucket.slots, r0, nr)

            def decode(b, sc, _segs=segs, _r0=r0):
                res = []
                for i, _s, a, srows in _segs:
                    rows = jax.lax.slice_in_dim(b, a - _r0, a - _r0 + srows,
                                                axis=0)
                    res.append(op(
                        rows[None], sc[i:i + 1], srows * kcommon.LANES,
                        (srows * kcommon.LANES,), interpret=self._interpret()))
                return tuple(res) + (sc[-1],)

            part = _ring_accumulate(chunk, (side,), decode, self.axes,
                                    self.n_workers)
            wtot = part[-1] if wtot is None else wtot
            for (i, _s, _a, _srows), arr in zip(segs, part[:-1]):
                outs[i].append(arr)
        result = []
        for s, parts in zip(bucket.slots, outs):
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            result.append(jax.lax.slice(flat, (0,), (s.size,)).reshape(s.shape))
        return result, wtot

    def wire_bytes(self, n_coords):
        # ring all-gather of the (padded) int8 payload to M-1 peers
        return float((self.n_workers - 1) * packed8_nbytes(n_coords))

    def scalar_bytes(self):
        # per-WORKER decode scales ride the same ring all-gather: M-1
        # incoming 4-B scalars per device (vs the all-reduced shared scalar
        # of the scaled_votes mode). The chunked ring re-ships them once
        # per chunk — ``uplink_ledger`` multiplies by ``ring_chunks``.
        # Elastic participation widens the slot to 8 B: the weighted decode
        # scale plus the raw weight (the participation side channel).
        per = 8.0 if self.participation is not None else 4.0
        return float((self.n_workers - 1) * per)

    def ring_chunks(self, n_coords):
        from repro.kernels import common as kcommon
        return len(_ring_chunk_spans(kcommon.canonical_rows(n_coords),
                                     self.ring_chunk_rows))

    def bucket_ring_chunks(self, bucket):
        return len(_ring_chunk_spans(bucket.rows, self.ring_chunk_rows))

    def _gather_hbm(self, rows: int) -> float:
        from repro.kernels import common as kcommon
        if self.ring_chunk_rows is None:
            return float(self.n_workers * rows * kcommon.LANES)
        max_nr = max(nr for _, nr in _ring_chunk_spans(rows, self.ring_chunk_rows))
        return float(2 * max_nr * kcommon.LANES)

    def gather_hbm_bytes(self, n_coords):
        from repro.kernels import common as kcommon
        return self._gather_hbm(kcommon.canonical_rows(n_coords))

    def bucket_gather_hbm_bytes(self, bucket):
        return self._gather_hbm(bucket.rows)


@dataclasses.dataclass(frozen=True)
class GolombWire(VoteWire):
    """All-gather of Golomb/RLE entropy-coded ternary payloads + fused
    decode-sum — the sub-2-bit variable-length wire (``kernels/golomb``).

    The message is a fixed-capacity uint8 byte stream sized at step-build
    time from the plan nonzero fraction ``p`` (``ref.golomb_rows``): coded
    zero-run gaps + sign bits behind an in-band header carrying the shipped/
    dropped nonzero counts (the length prefix — a gathered buffer is
    self-describing). Static capacity keeps the exchange a fixed-shape
    all-gather, so the byte ledger (capacity padding included) equals the
    traced collective exactly; messages denser than plan truncate at
    capacity with the dropped count in the header, and configurations where
    the capacity loses to pack2 already failed loudly at build time.

    With ``ring_chunk_rows`` set, the gather becomes the ppermute ring. The
    coded stream is not row-addressable mid-stream, so golomb chunks on
    STREAM boundaries: a per-leaf exchange rings its whole capacity stream
    as one chunk; a bucket rings groups of consecutive whole slots
    (``_slot_groups`` — each slot is its own self-describing stream).
    int32 accumulation, bitwise the monolithic gather."""

    backend: Optional[str] = None
    p: float = 0.05
    ring_chunk_rows: Optional[int] = None

    name = "allgather_golomb"
    native_format = "golomb"

    @scoped(COUNTERS)
    def message_nnz(self, values):
        # the in-band header IS the count: bytes 0-3, uint32 little-endian
        # (shipped nonzeros — what the server's vote sum will see)
        h = values.reshape(-1)[:4].astype(jnp.float32)
        return h[0] + h[1] * 256.0 + h[2] * 65536.0 + h[3] * 16777216.0

    def message_dropped(self, values):
        """Nonzeros truncated at capacity (header bytes 4-7) — the overflow
        telemetry a caller can surface when realized nnz outruns plan p."""
        h = values.reshape(-1)[4:8].astype(jnp.float32)
        return h[0] + h[1] * 256.0 + h[2] * 65536.0 + h[3] * 16777216.0

    @scoped(EXCHANGE)
    def exchange(self, values, size, shape, *, scale=None):
        if scale is not None:
            raise ValueError(
                "the golomb vote wire exchanges entropy-coded ternary votes; "
                "a decode scale inside the exchange is a pack8-wire concept")
        if self.ring_chunk_rows is not None:
            # one leaf = one self-describing capacity stream = one chunk
            def decode(b):
                return _golomb_decode_sum(b[None], size, shape, p=self.p,
                                          backend=self.backend)

            total = _ring_accumulate(values, (), decode, self.axes,
                                     self.n_workers)
            return total.astype(_sum_dtype(self.n_workers))
        gathered = jax.lax.all_gather(values, self.axes, axis=0, tiled=False)
        total = _golomb_decode_sum(gathered, size, shape, p=self.p,
                                   backend=self.backend)
        return total.astype(_sum_dtype(self.n_workers))

    @scoped(EXCHANGE)
    def exchange_bucket(self, payload, bucket, *, scale=None):
        """ONE all-gather of the whole coded bucket, then per-slot fused
        decode-sums on the gathered row slices. Slots are whole capacity
        streams (their own headers), so each slice decodes exactly as the
        per-leaf wire message — there is no whole-bucket decode to split:
        the coded stream, unlike pack2 rows, is not coordinate-addressable.
        The ring path chunks on whole-slot groups for the same reason."""
        if scale is not None:
            raise ValueError(
                "the golomb vote wire exchanges entropy-coded ternary votes; "
                "a decode scale inside the exchange is a pack8-wire concept")
        if self.ring_chunk_rows is not None:
            return self._ring_exchange_bucket(payload, bucket)
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        out = []
        for s in bucket.slots:
            rows = jax.lax.slice_in_dim(gathered, s.row_start,
                                        s.row_start + s.rows, axis=1)
            total = _golomb_decode_sum(rows, s.size, s.shape, p=self.p,
                                       backend=self.backend)
            out.append(total.astype(_sum_dtype(self.n_workers)))
        return out

    def _ring_exchange_bucket(self, payload, bucket):
        """Ring the bucket in whole-slot groups: each group's contiguous row
        span is one chunk whose decode is a tuple of per-slot fused
        decode-sums (slots carry their own headers, so a group chunk is a
        concatenation of self-contained streams)."""
        slot_pos = {s: i for i, s in enumerate(bucket.slots)}
        out = [None] * len(bucket.slots)
        for g in _slot_groups(bucket.slots, self.ring_chunk_rows):
            r0 = g[0].row_start
            g_rows = sum(s.rows for s in g)
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + g_rows, axis=0)

            def decode(b, _g=g, _r0=r0):
                res = []
                for s in _g:
                    rows = jax.lax.slice_in_dim(
                        b, s.row_start - _r0,
                        s.row_start - _r0 + s.rows, axis=0)
                    res.append(_golomb_decode_sum(rows[None], s.size, s.shape,
                                                  p=self.p,
                                                  backend=self.backend))
                return tuple(res)

            part = _ring_accumulate(chunk, (), decode, self.axes,
                                    self.n_workers)
            for s, arr in zip(g, part):
                out[slot_pos[s]] = arr.astype(_sum_dtype(self.n_workers))
        return out

    @scoped(EXCHANGE)
    def exchange_weighted(self, values, size, shape, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the golomb vote wire exchanges entropy-coded ternary votes; "
                "a decode scale inside the exchange is a pack8-wire concept")
        w1 = jnp.asarray(weight, jnp.float32).reshape((1,))
        if self.ring_chunk_rows is not None:
            # one leaf = one self-describing capacity stream = one chunk;
            # the (1,) weight rides the same ring as the side channel
            def decode(b, wv):
                s = _golomb_decode_wsum(b[None], wv, size, shape, p=self.p,
                                        backend=self.backend)
                return (s, jnp.sum(wv))

            return _ring_accumulate(values, (w1,), decode, self.axes,
                                    self.n_workers)
        gathered = jax.lax.all_gather(values, self.axes, axis=0, tiled=False)
        wvec = jax.lax.all_gather(w1, self.axes, axis=0,
                                  tiled=False).reshape(-1)
        wv = _golomb_decode_wsum(gathered, wvec, size, shape, p=self.p,
                                 backend=self.backend)
        return wv, jnp.sum(wvec)

    @scoped(EXCHANGE)
    def exchange_bucket_weighted(self, payload, bucket, *, weight, scale=None):
        self._require_participation()
        if scale is not None:
            raise ValueError(
                "the golomb vote wire exchanges entropy-coded ternary votes; "
                "a decode scale inside the exchange is a pack8-wire concept")
        w1 = jnp.asarray(weight, jnp.float32).reshape((1,))
        if self.ring_chunk_rows is not None:
            return self._ring_exchange_bucket_weighted(payload, w1, bucket)
        gathered = jax.lax.all_gather(payload, self.axes, axis=0, tiled=False)
        wvec = jax.lax.all_gather(w1, self.axes, axis=0,
                                  tiled=False).reshape(-1)
        out = []
        for s in bucket.slots:
            rows = jax.lax.slice_in_dim(gathered, s.row_start,
                                        s.row_start + s.rows, axis=1)
            out.append(_golomb_decode_wsum(rows, wvec, s.size, s.shape,
                                           p=self.p, backend=self.backend))
        return out, jnp.sum(wvec)

    def _ring_exchange_bucket_weighted(self, payload, w1, bucket):
        """Weighted slot-group ring: the (1,) weight rides every group
        chunk; raw weights accumulate around the ring into the realized
        participation total."""
        slot_pos = {s: i for i, s in enumerate(bucket.slots)}
        out = [None] * len(bucket.slots)
        wtot = None
        for g in _slot_groups(bucket.slots, self.ring_chunk_rows):
            r0 = g[0].row_start
            g_rows = sum(s.rows for s in g)
            chunk = jax.lax.slice_in_dim(payload, r0, r0 + g_rows, axis=0)

            def decode(b, wv, _g=g, _r0=r0):
                res = []
                for s in _g:
                    rows = jax.lax.slice_in_dim(
                        b, s.row_start - _r0,
                        s.row_start - _r0 + s.rows, axis=0)
                    res.append(_golomb_decode_wsum(rows[None], wv, s.size,
                                                   s.shape, p=self.p,
                                                   backend=self.backend))
                return tuple(res) + (jnp.sum(wv),)

            part = _ring_accumulate(chunk, (w1,), decode, self.axes,
                                    self.n_workers)
            wtot = part[-1] if wtot is None else wtot
            for s, arr in zip(g, part[:-1]):
                out[slot_pos[s]] = arr
        return out, wtot

    def weight_bytes(self):
        # the (1,) f32 effective weight gathered from M-1 peers next to the
        # coded payload — the elastic side channel
        if self.participation is None:
            return 0.0
        return float((self.n_workers - 1) * 4.0)

    def wire_bytes(self, n_coords):
        # ring all-gather of the capacity-padded coded payload to M-1 peers
        return float((self.n_workers - 1)
                     * golomb_payload_nbytes(n_coords, self.p))

    def bucket_payload_bytes(self, n_coords, rows=None):
        # bucket rows are capacity rows (plan-time, per slot), NOT coordinate
        # rows — bill exactly the (rows, 128) uint8 buffer the gather ships
        assert rows is not None, \
            "golomb bucket ledger needs the bucket's payload row count"
        from repro.kernels.golomb.ref import ROW_BYTES
        return float((self.n_workers - 1) * rows * ROW_BYTES)

    def payload_rows(self, n_coords: int) -> int:
        """Static capacity rows of one n-coordinate leaf at the wire's plan
        fraction — the bucket plan's ``rows_fn`` for this wire."""
        from repro.kernels.golomb.ref import golomb_rows
        return golomb_rows(n_coords, self.p)

    def bucket_ring_chunks(self, bucket):
        return len(_slot_groups(bucket.slots, self.ring_chunk_rows))

    def gather_hbm_bytes(self, n_coords):
        from repro.kernels.golomb.ref import ROW_BYTES, golomb_rows
        rows = golomb_rows(n_coords, self.p)
        if self.ring_chunk_rows is None:
            return float(self.n_workers * rows * ROW_BYTES)
        # a per-leaf stream is one chunk regardless of size (not row-
        # addressable), so the ring holds ~2 whole streams — still an M/2
        # residency win over the monolithic gather
        return float(2 * rows * ROW_BYTES)

    def bucket_gather_hbm_bytes(self, bucket):
        from repro.kernels.golomb.ref import ROW_BYTES
        if self.ring_chunk_rows is None:
            return float(self.n_workers * bucket.rows * ROW_BYTES)
        max_rows = max(sum(s.rows for s in g)
                       for g in _slot_groups(bucket.slots, self.ring_chunk_rows))
        return float(2 * max_rows * ROW_BYTES)


def make_vote_wire(impl: str, axes: Sequence[str], mesh=None, *,
                   backend: Optional[str] = None,
                   wire_format: str = "pack2",
                   golomb_p: Optional[float] = None,
                   ring_chunk_rows: Optional[int] = None,
                   participation: Optional[ParticipationSpec] = None) -> VoteWire:
    """Build the wire for ``impl`` over the worker ``axes`` at step-build time.

    Axis sizes come from ``mesh.shape`` when a mesh is given (the builders'
    path — errors surface before tracing), else from the ambient axis env
    (valid inside shard_map). ``backend`` steers the packed wires' decode-sum
    dispatch exactly like the engine's kernel backends. ``wire_format`` is the
    negotiated payload format (``engine.wire_payload_format``): ``pack2``
    selects the ternary wires, ``golomb`` the entropy-coded ternary gather
    (``allgather_packed`` impl only — a fabric psum cannot sum byte streams;
    ``golomb_p`` is its plan-time nonzero fraction, required), ``pack8`` the
    8-bit level gather (``allgather_packed`` only — levels quantized against
    per-worker norms cannot be reduced on the fabric). ``ring_chunk_rows``
    (gather wires only; a positive sublane multiple, e.g.
    ``DEFAULT_RING_CHUNK_ROWS``) switches the gather to the chunked
    ppermute ring — see the module docstring and ``engine.
    resolve_ring_chunk_rows`` for the negotiated path. ``participation``
    (a ``ParticipationSpec``) arms the elastic weighted-exchange family —
    per-worker weights are validated against the realized worker count here,
    at build time.
    """
    axes = tuple(axes)
    if participation is not None and not isinstance(participation,
                                                    ParticipationSpec):
        raise TypeError(
            f"participation must be a ParticipationSpec, got "
            f"{type(participation).__name__}")
    if impl not in VOTE_IMPLS:
        raise ValueError(f"unknown vote_impl {impl!r}; known: {VOTE_IMPLS}")
    if impl == "hier" and len(axes) != 2:
        raise ValueError(
            f"vote_impl='hier' needs exactly two worker axes (outer, inner) "
            f"— e.g. ('pod', 'data') — got {axes!r}. Use vote_impl='psum' "
            f"for a flat worker domain; silently substituting the flat wire "
            f"here would misreport the hierarchical byte ledger.")
    if wire_format not in ("pack2", "golomb", "pack8"):
        raise ValueError(
            f"unknown wire payload format {wire_format!r}; the vote wires "
            f"speak 'pack2'/'golomb' (ternary) or 'pack8' (8-bit levels) — "
            f"the float format rides the decoded psum, not a VoteWire")
    if wire_format == "pack8" and impl != "allgather_packed":
        raise ValueError(
            f"the pack8 wire needs vote_impl='allgather_packed' (per-worker "
            f"decode scales ride the gather; a fabric psum cannot sum levels "
            f"quantized against different norms), got {impl!r} — "
            f"engine.wire_mode falls back to the decoded wire there")
    if wire_format == "golomb":
        if impl != "allgather_packed":
            raise ValueError(
                f"the golomb wire needs vote_impl='allgather_packed' (a "
                f"fabric psum cannot reduce variable-length byte streams), "
                f"got {impl!r} — engine.wire_payload_format falls back to "
                f"int8 psum votes there")
        if golomb_p is None:
            raise ValueError(
                "the golomb wire needs golomb_p (the plan-time nonzero "
                "fraction that sizes its static capacity) — see "
                "engine.resolve_golomb_p")
        if not 0.0 < float(golomb_p) < 1.0:
            raise ValueError(
                f"golomb plan fraction must be in (0,1), got {golomb_p}")
    if ring_chunk_rows is not None:
        if impl != "allgather_packed":
            raise ValueError(
                f"ring_chunk_rows is a gather-wire concept (it chunks the "
                f"gathered payload) — vote_impl={impl!r} reduces on the "
                f"fabric and never materializes a gathered tensor; use "
                f"vote_impl='allgather_packed', or drop the ring knob")
        from repro.kernels import common as kcommon
        r = int(ring_chunk_rows)
        if r <= 0 or r % kcommon.SUBLANE_PAD != 0:
            raise ValueError(
                f"ring_chunk_rows must be a positive multiple of the "
                f"sublane tile ({kcommon.SUBLANE_PAD}) so every chunk stays "
                f"a valid kernel grid, got {ring_chunk_rows!r}")
        ring_chunk_rows = r
    sizes = tuple(int(mesh.shape[a]) for a in axes) if mesh is not None \
        else tuple(jax.lax.axis_size(a) for a in axes)
    # one build-time validation point: every per-size /n in the byte ledgers
    # (and the worker count itself) is safe downstream of this check
    if not axes or any(s < 1 for s in sizes):
        raise ValueError(
            f"vote wire needs >= 1 worker: axes {axes!r} have sizes {sizes!r}")
    n = 1
    for s in sizes:
        n *= s
    if participation is not None:
        # weights must cover the realized fleet — fail before tracing
        participation.weights_array(n)
    if wire_format == "pack8":
        return Pack8Wire(axes=axes, n_workers=n, backend=backend,
                         ring_chunk_rows=ring_chunk_rows,
                         participation=participation)
    if wire_format == "golomb":
        return GolombWire(axes=axes, n_workers=n, backend=backend,
                          p=float(golomb_p), ring_chunk_rows=ring_chunk_rows,
                          participation=participation)
    if impl == "hier":
        return HierVoteWire(axes=axes, n_workers=n,
                            inner_size=sizes[1], outer_size=sizes[0],
                            participation=participation)
    if impl == "allgather_packed":
        return PackedVoteWire(axes=axes, n_workers=n, backend=backend,
                              ring_chunk_rows=ring_chunk_rows,
                              participation=participation)
    return VoteWire(axes=axes, n_workers=n, participation=participation)
