"""Jaxpr auditor: traceable-program rules over recursively-walked jaxprs.

The walker (``iter_eqns``) is the generalization of the old
``kernels/common.hbm_elems`` visitor (which now delegates here). It descends
into every sub-jaxpr an equation carries — scan/while/cond/pjit bodies,
``custom_jvp_call``/``custom_vjp_call``/``closed_call`` and their
post-AD ``*_jaxpr`` forms via an explicit primitive->param map, plus a generic
sweep over list/tuple/dict-valued params for anything the map doesn't name —
but never into a ``pallas_call`` kernel body, whose values live in VMEM
registers, not HBM.

Rules:

  NoHbmIntermediate(dtype, limit)  — at most ``limit`` elements of ``dtype``
      materialized between ops. Declared per-``CompressorSpec``
      (``spec.hbm_limits``); ``check_fused_uplink`` runs a spec's declared
      rules against its own fused wire op — the declarative replacement for
      every hand-written int8/int32 pin.
  CollectiveCensus(axis_sizes, tolerance) — tally psum/all_gather/ppermute/...
      payload bytes of a traced step under the ring-collective byte model at
      *hypothetical* worker-axis sizes, and pin them against the VoteWire
      ledger. Tracing happens on a 1-device mesh (tier-1); the eqn structure
      is M-independent, so evaluating the model at M=16 gives a non-vacuous
      byte pin without multi-device hardware. M must stay <= 127 so the
      build-time ``_sum_dtype`` bucket (int8) matches the hypothetical M.
  DtypePromotionDrift(banned, min_elems) — flags ``banned``-dtype tensors of
      >= min_elems elements on a declared-narrow (e.g. bf16) leaf path: a
      full-size f32 HBM intermediate on a bf16 uplink is a silent 2x traffic
      regression.
  MaskedPayloadZero — every untiled >= 2-element integer gather payload
      (all_gather/ppermute) must trace back to a ``select_n`` participation
      mask through shape-preserving primitives and across scope boundaries:
      a non-reporting worker's bytes still ride the SPMD gather, so they
      must be exact zeros or they vote.
"""

from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Callable, Iterable, Iterator, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.framework import Finding, Rule

from jax.extend import core as jcore


# ---------------------------------------------------------------------------
# The walker
# ---------------------------------------------------------------------------

#: primitive -> param keys that carry its sub-jaxprs. The generic param sweep
#: below finds ClosedJaxpr/Jaxpr values wherever they sit, so most primitives
#: need no entry; the explicit map exists for the call-like primitives whose
#: descent is a *contract* (the old walker's blind spot): custom_jvp/custom_vjp
#: calls, closed_call, and the post-partial-eval ``*_call_jaxpr`` forms.
EXPLICIT_SUB_JAXPRS: dict[str, tuple] = {
    "custom_jvp_call": ("call_jaxpr",),
    "custom_jvp_call_jaxpr": ("fun_jaxpr",),
    "custom_vjp_call": ("call_jaxpr",),
    "custom_vjp_call_jaxpr": ("fun_jaxpr",),
    "closed_call": ("call_jaxpr",),
    "core_call": ("call_jaxpr",),
    "remat2": ("jaxpr",),
    "checkpoint": ("jaxpr",),
    "pjit": ("jaxpr",),
    "scan": ("jaxpr",),
    "while": ("cond_jaxpr", "body_jaxpr"),
    "cond": ("branches",),
}


def _param_jaxprs(value, seen: set) -> Iterator:
    """Yield every (unvisited) Jaxpr reachable from one param value:
    ClosedJaxpr/Jaxpr directly, or nested in lists/tuples/dicts."""
    if isinstance(value, jcore.ClosedJaxpr):
        value = value.jaxpr
    if isinstance(value, jcore.Jaxpr):
        if id(value) not in seen:
            seen.add(id(value))
            yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _param_jaxprs(v, seen)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _param_jaxprs(v, seen)


def sub_jaxprs(eqn) -> Iterator:
    """All sub-jaxprs of one equation: the explicit contract params first,
    then the generic sweep (deduplicated, so nothing is visited twice)."""
    seen: set = set()
    for key in EXPLICIT_SUB_JAXPRS.get(eqn.primitive.name, ()):
        if key in eqn.params:
            yield from _param_jaxprs(eqn.params[key], seen)
    for value in eqn.params.values():
        yield from _param_jaxprs(value, seen)


def iter_eqns(jaxpr, *, enter_pallas: bool = False) -> Iterator:
    """Depth-first over every equation of ``jaxpr`` and its sub-jaxprs.
    ``enter_pallas=False`` (the HBM view) stops at pallas_call boundaries."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not enter_pallas:
            continue
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, enter_pallas=enter_pallas)


def _as_jaxpr(fn_or_jaxpr, args):
    if isinstance(fn_or_jaxpr, jcore.ClosedJaxpr):
        return fn_or_jaxpr.jaxpr
    if isinstance(fn_or_jaxpr, jcore.Jaxpr):
        return fn_or_jaxpr
    return jax.make_jaxpr(fn_or_jaxpr)(*args).jaxpr


def hbm_usage(fn, *args, dtypes: Sequence = (jnp.int8,)) -> dict:
    """Element count per dtype of arrays materialized *between* ops (HBM-level
    traffic) when tracing ``fn(*args)``. Pallas kernel bodies excluded."""
    want = {jnp.dtype(d): 0 for d in dtypes}
    for eqn in iter_eqns(_as_jaxpr(fn, args)):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt in want:
                want[dt] += math.prod(aval.shape)
    return want


def hbm_elems(fn, *args, dtype=jnp.int8) -> int:
    """Single-dtype view of ``hbm_usage`` — the engine of the historical
    ``kernels.common.int8_hbm_elems``/``int32_hbm_elems`` pins."""
    return hbm_usage(fn, *args, dtypes=(dtype,))[jnp.dtype(dtype)]


# ---------------------------------------------------------------------------
# NoHbmIntermediate — the per-spec fused-uplink contract
# ---------------------------------------------------------------------------

class NoHbmIntermediate(Rule):
    """At most ``limit`` elements of ``dtype`` may hit HBM in the traced
    program. ``limit=0`` is the fused-kernel guarantee (gradient -> wire bytes
    in one pass); qsgd8 declares ``("int32", 1)`` — the single scatter-start
    index of the canonical-view pad, never an O(n) level tensor."""

    name = "no-hbm-intermediate"
    description = "fused ops must not materialize banned-dtype HBM tensors"

    def __init__(self, dtype, limit: int = 0):
        self.dtype = jnp.dtype(dtype)
        self.limit = int(limit)

    def check(self, label: str, fn, *args) -> list:
        count = hbm_elems(fn, *args, dtype=self.dtype)
        if count > self.limit:
            return [self.finding(
                label,
                f"{count} {self.dtype.name} elements materialized at the HBM "
                f"level (declared limit {self.limit})")]
        return []


def spec_hbm_rules(spec) -> tuple:
    """The NoHbmIntermediate rules one CompressorSpec row declares."""
    return tuple(NoHbmIntermediate(dtype, limit) for dtype, limit in spec.hbm_limits)


def check_fused_uplink(spec, g, *, seed: int = 7, param=None) -> list:
    """Run a spec's declared HBM rules against its own fused wire op.

    ``param`` defaults to the spec's local scale statistic (scale-carrying
    rows) or 1.0 (scale-free rows) — the counts are structural, not
    param-dependent. The seed is passed as uint32 exactly as the engine
    supplies it, so no stray i32->u32 scalar conversion muddies the count.
    """
    if spec.fused_pack_op is None:
        return []
    if param is None:
        param = spec.local_scale(g) if spec.local_scale is not None else 1.0
    findings: list = []
    for rule in spec_hbm_rules(spec):
        findings += rule.check(
            f"{spec.name}.fused_pack_op",
            lambda x: spec.fused_pack_op(x, param, jnp.uint32(seed),
                                         interpret=True), g)
    return findings


# ---------------------------------------------------------------------------
# CollectiveCensus — collective payload bytes vs the VoteWire ledger
# ---------------------------------------------------------------------------

#: ring-model family per collective primitive (mirrors launch/hlo_stats.py and
#: the VoteWire ledgers — one byte model, three places that must agree)
COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
                    "ppermute", "reduce_scatter", "psum_scatter")

#: named-axis primitives that move NO payload over the fabric: device-id
#: introspection and the replication-adjustment markers shard_map's
#: check_rep/check_vma machinery inserts. Everything else that names a mesh
#: axis and carries bytes is either modeled (COLLECTIVE_PRIMS) or an
#: *unknown* collective — recorded on ``Census.unknown`` and turned into a
#: blocking Finding by the census rule, never an uncounted zero.
NONWIRE_PRIMS = ("axis_index", "pvary", "pbroadcast")


def _named_axes(eqn) -> tuple:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One collective equation: what ships, over which named axes.

    ``trips`` is how many times the equation runs per step — the product of
    the ``scan`` lengths enclosing it (a collective inside the streamed
    backward scan launches once per superblock). ``tiled`` marks the
    all_gather variant the FSDP parameter path uses (``tiled=True``); wire
    exchanges gather with ``tiled=False``, so the flag separates parameter
    movement from uplink payload."""

    primitive: str
    axes: tuple
    in_elems: int      # total operand elements (1 => scalar protocol traffic)
    in_bytes: int      # total operand payload bytes
    out_bytes: int
    trips: int = 1
    tiled: bool = False

    def group_size(self, axis_sizes: Mapping[str, int]) -> int:
        m = 1
        for a in self.axes:
            m *= int(axis_sizes[a])
        return m

    def ring_bytes(self, axis_sizes: Mapping[str, int]) -> float:
        """Per-device wire bytes under the ring model at the given axis sizes
        (the same first principles as hlo_stats and the VoteWire ledgers)."""
        m = self.group_size(axis_sizes)
        if m <= 1:
            return 0.0
        if self.primitive in ("psum", "pmax", "pmin"):      # all-reduce
            return 2.0 * (m - 1) / m * self.in_bytes
        if self.primitive == "all_gather":                  # transmit to M-1 peers
            return float((m - 1) * self.in_bytes)
        if self.primitive in ("reduce_scatter", "psum_scatter"):
            return float((m - 1) * self.out_bytes)
        if self.primitive == "all_to_all":
            return (m - 1) / m * self.in_bytes
        # ppermute: the ring-pipelined gather's hop primitive. ONE traced
        # ppermute is an M-1-hop ring (the hop loop is a while_loop, whose
        # body the walker bills at trips=1), each hop shipping the full
        # chunk — so a chunk's ring costs (M-1) x chunk bytes, and summing
        # over chunks reproduces the gather wire's (M-1) x payload exactly.
        assert self.primitive == "ppermute", self.primitive
        return float((m - 1) * self.in_bytes)


@dataclasses.dataclass(frozen=True)
class Census:
    """Every collective of one traced program, byte-costable at any
    hypothetical axis sizes. ``unknown`` holds payload-carrying named-axis
    equations the byte model does NOT cover — they are excluded from every
    byte/count sum (no model to bill them under) and exist to be surfaced
    loudly by the census rule, not silently zeroed."""

    records: tuple
    unknown: tuple = ()

    def counts(self) -> Counter:
        return Counter({p: sum(r.trips for r in self.records if r.primitive == p)
                        for p in {r.primitive for r in self.records}})

    def _select(self, *, min_elems: int = 0, max_elems: Optional[int] = None,
                include_tiled: bool = True):
        return (r for r in self.records
                if r.in_elems >= min_elems
                and (max_elems is None or r.in_elems <= max_elems)
                and (include_tiled or not r.tiled))

    def total_bytes(self, axis_sizes, *, min_elems: int = 0,
                    max_elems: Optional[int] = None,
                    include_tiled: bool = True) -> float:
        return sum(r.trips * r.ring_bytes(axis_sizes)
                   for r in self._select(min_elems=min_elems,
                                         max_elems=max_elems,
                                         include_tiled=include_tiled))

    def payload_bytes(self, axis_sizes) -> float:
        """Array-payload traffic (>= 2 elements): the wire-ledger term.
        FSDP parameter gathers (``tiled=True``) are parameter movement, not
        uplink — the VoteWire ledger does not bill them, so neither does the
        payload view."""
        return self.total_bytes(axis_sizes, min_elems=2, include_tiled=False)

    def scalar_bytes(self, axis_sizes) -> float:
        """Scalar protocol traffic: decode scales, n_sel/loss/nnz metrics."""
        return self.total_bytes(axis_sizes, max_elems=1)

    def payload_count(self) -> int:
        """Launches per step of array-payload (>= 2 element, untiled)
        collectives — the uplink launch count the bucketed wire collapses."""
        return sum(r.trips for r in self._select(min_elems=2,
                                                 include_tiled=False))

    def scalar_count(self) -> int:
        """Launches per step of scalar (<= 1 element) collectives."""
        return sum(r.trips for r in self._select(max_elems=1))


def collective_census(fn, *args) -> Census:
    """Trace ``fn(*args)`` (or take a ready jaxpr) and record every
    collective equation, descending like the HBM walker. Descent through a
    ``scan`` multiplies ``trips`` by the scan length, so a collective inside
    the streamed backward scan is billed once per superblock; ``while`` trip
    counts are unknowable statically and stay at 1 — which is exactly the
    ring gather's billing contract: its hop loop is a while_loop whose single
    ppermute models the whole M-1-hop ring (``CollectiveRecord.ring_bytes``).

    A payload-carrying equation that NAMES a mesh axis but is neither a
    modeled collective (``COLLECTIVE_PRIMS``) nor a known payload-free prim
    (``NONWIRE_PRIMS``) lands on ``Census.unknown`` — the census rule blocks
    on it, because an unmodeled collective silently billed at zero bytes is
    how a ledger pin rots."""
    records = []
    unknown = []

    def walk(jaxpr, trips: int):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                in_avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
                out_avals = [v.aval for v in eqn.outvars if hasattr(v, "aval")]
                records.append(CollectiveRecord(
                    primitive=name,
                    axes=_named_axes(eqn),
                    in_elems=sum(math.prod(a.shape) for a in in_avals),
                    in_bytes=sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                                 for a in in_avals),
                    out_bytes=sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                                  for a in out_avals),
                    trips=trips,
                    tiled=bool(eqn.params.get("tiled", False)),
                ))
            elif name not in NONWIRE_PRIMS and _named_axes(eqn):
                in_avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
                in_bytes = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                               for a in in_avals)
                if in_bytes > 0:
                    unknown.append(CollectiveRecord(
                        primitive=name,
                        axes=_named_axes(eqn),
                        in_elems=sum(math.prod(a.shape) for a in in_avals),
                        in_bytes=in_bytes,
                        out_bytes=0,
                        trips=trips,
                    ))
            if name == "pallas_call":
                continue
            sub_trips = trips
            if name == "scan":
                sub_trips = trips * int(eqn.params.get("length", 1))
            for sub in sub_jaxprs(eqn):
                walk(sub, sub_trips)

    walk(_as_jaxpr(fn, args), 1)
    return Census(records=tuple(records), unknown=tuple(unknown))


class CollectiveCensus(Rule):
    """Pin a traced step's collective bytes against the VoteWire ledger.

    Array payloads (>= 2 elements) must equal the ledger's ``wire_bytes`` sum
    exactly (within ``tolerance`` — 0 by default: the ledger is built from the
    same padded buffer sizes the collectives ship). Scalar traffic must cover
    at least the ledger's ``scalar_bytes`` protocol term; the census may
    legitimately exceed it with metric reductions (n_sel / loss / nnz), which
    the ledger deliberately does not bill to the wire.
    """

    name = "collective-census"
    description = "traced collective bytes must match the VoteWire ledger"

    def __init__(self, axis_sizes: Mapping[str, int], tolerance: float = 0.0):
        self.axis_sizes = dict(axis_sizes)
        self.tolerance = float(tolerance)

    def check(self, label: str, census: Census, *, ledger_payload: float,
              ledger_scalar_min: float = 0.0) -> list:
        findings = []
        if census.unknown:
            names = ", ".join(sorted({
                f"{r.primitive}[{','.join(r.axes)}]({r.in_bytes}B)"
                for r in census.unknown}))
            findings.append(self.finding(
                label,
                f"{len(census.unknown)} payload-carrying collective "
                f"equation(s) the byte model does not cover: {names} — an "
                f"unmodeled collective billed at zero bytes voids the "
                f"ledger pin; teach CollectiveRecord.ring_bytes its model "
                f"(or add a payload-free prim to NONWIRE_PRIMS)"))
        payload = census.payload_bytes(self.axis_sizes)
        tol = self.tolerance * max(abs(ledger_payload), 1.0)
        if abs(payload - ledger_payload) > tol:
            findings.append(self.finding(
                label,
                f"collective array-payload bytes {payload:.1f} != VoteWire "
                f"ledger {ledger_payload:.1f} at axis sizes "
                f"{self.axis_sizes} (census: {dict(census.counts())})"))
        scal = census.scalar_bytes(self.axis_sizes)
        if scal + 1e-9 < ledger_scalar_min:
            findings.append(self.finding(
                label,
                f"scalar collective bytes {scal:.1f} do not cover the "
                f"ledger's protocol scalars {ledger_scalar_min:.1f}"))
        return findings


class CollectiveCountBudget(Rule):
    """Pin a traced step's collective LAUNCH counts, not just its bytes.

    Launch count is the latency story the byte census cannot see: a hundred
    tiny exchanges and one bucket of the same total bytes cost the same under
    the ring byte model, but each launch pays fixed fabric latency. The rule
    pins the array-payload launch count to the mode's exact budget (per-leaf:
    one-ish per leaf; bucketed: one-ish per bucket — the builder's formula),
    and caps the scalar protocol launches. Exceeding either is a regression
    to chatty-wire behavior; a payload count BELOW budget means the ledger
    formula itself drifted from the program — both block.
    """

    name = "collective-count"
    description = "traced collective launch counts must match the mode budget"

    def check(self, label: str, census: Census, *, expected_payload: int,
              max_scalar: Optional[int] = None) -> list:
        findings = []
        got = census.payload_count()
        if got != int(expected_payload):
            findings.append(self.finding(
                label,
                f"{got} array-payload collective launches per step, budget "
                f"says exactly {expected_payload} "
                f"(census: {dict(census.counts())})"))
        if max_scalar is not None:
            scal = census.scalar_count()
            if scal > int(max_scalar):
                findings.append(self.finding(
                    label,
                    f"{scal} scalar collective launches per step exceed the "
                    f"protocol budget {max_scalar}"))
        return findings


class EntropyWireBudget(Rule):
    """Blocking compression-ratio floor for the entropy-coded uplink.

    The golomb wire only earns its place if its HONEST billed bytes — static
    capacity rows including the percentile padding tax, exactly what the
    fixed-shape gather ships and the ledger/census pin — undercut the flat
    2-bit wire by at least ``min_ratio`` at the paper-regime plan sparsity.
    A capacity formula drifting loose (over-padded rows), a row-alignment
    regression, or a bucket plan billing coordinate-count fiction would all
    silently eat the sub-2-bit win; this rule blocks on it, the byte twin of
    ``CollectiveCountBudget``'s launch-ratio floor.
    """

    name = "entropy-wire-budget"
    description = ("golomb wire bytes (capacity padding included) must beat "
                   "the flat 2-bit wire by the configured floor")

    def __init__(self, min_ratio: float = 2.0):
        self.min_ratio = float(min_ratio)

    def check(self, label: str, *, golomb_bytes: float,
              pack2_bytes: float) -> list:
        if golomb_bytes * self.min_ratio > pack2_bytes:
            ratio = pack2_bytes / max(golomb_bytes, 1e-9)
            return [self.finding(
                label,
                f"golomb wire bills {golomb_bytes:.0f} B vs {pack2_bytes:.0f} "
                f"B on the flat 2-bit wire — ratio {ratio:.2f}x is under the "
                f"{self.min_ratio:.1f}x floor")]
        return []


class GatherHbmBudget(Rule):
    """Blocking peak-HBM floor for the ring-pipelined gather.

    The ring wire's whole point is residency: the monolithic gather holds
    M x payload of gathered bytes in HBM before decoding, the chunked
    ppermute ring holds ~2 chunks. This rule pins that win via the honest
    ``gather_hbm_bytes`` ledger — ring peak HBM must undercut the monolithic
    gather's by at least ``min_ratio`` (M/2 at the hypothetical census M:
    2 chunks vs M payloads, with chunk <= payload). A chunk-framing
    regression (chunks growing past the payload, a ledger billing the ring
    at gather residency) blocks here; wire BYTES are intentionally not part
    of this rule — the ring moves the same bytes, only the residency drops.
    """

    name = "gather-hbm-budget"
    description = ("ring gather peak payload HBM must undercut the "
                   "monolithic gather by the configured floor")

    def __init__(self, min_ratio: float):
        self.min_ratio = float(min_ratio)

    def check(self, label: str, *, ring_bytes: float,
              mono_bytes: float) -> list:
        if ring_bytes * self.min_ratio > mono_bytes:
            ratio = mono_bytes / max(ring_bytes, 1e-9)
            return [self.finding(
                label,
                f"ring gather peaks at {ring_bytes:.0f} B of gathered "
                f"payload HBM vs {mono_bytes:.0f} B monolithic — ratio "
                f"{ratio:.2f}x is under the {self.min_ratio:.1f}x floor")]
        return []


# ---------------------------------------------------------------------------
# MaskedPayloadZero — a non-reporting worker's gather payload must be zeros
# ---------------------------------------------------------------------------

#: primitives a payload's ZEROS survive unchanged — the mask backtracker
#: walks through these from a gathered operand toward its mask gate: shape/
#: layout moves, dtype casts, bucket assembly (concatenate/pad), and the
#: ring's own hop primitive. Anything else (an add of fresh data, an iota)
#: breaks zero-provenance and the search stops on that path.
MASK_PASS_THROUGH = frozenset({
    "slice", "dynamic_slice", "reshape", "convert_element_type",
    "broadcast_in_dim", "transpose", "squeeze", "expand_dims", "rev",
    "concatenate", "pad", "copy", "ppermute",
})

#: collective primitives whose operand IS a worker's shipped uplink payload
#: (the monolithic gather and the chunked ring's hop)
GATHER_PRIMS = ("all_gather", "ppermute")


def _is_int_payload(aval) -> bool:
    """Is this aval a >= 2-element integer buffer — the shape of a packed
    wire payload? The f32 scale/weight side channels are value-carrying by
    design (a non-reporter's weight slot ships its 0.0) and exempt."""
    dt = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    return (dt is not None and shape is not None
            and jnp.issubdtype(dt, jnp.integer)
            and math.prod(shape) >= 2)


def _producers(jaxpr, cache: dict) -> dict:
    """id(outvar) -> producing eqn table for one jaxpr (memoized)."""
    tbl = cache.get(id(jaxpr))
    if tbl is None:
        tbl = {}
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                tbl[id(v)] = eqn
        cache[id(jaxpr)] = tbl
    return tbl


def _map_invar_out(eqn, sub, idx):
    """The outer operand feeding sub-jaxpr invar ``idx`` of call-like
    ``eqn`` (None if unmappable). ``while`` splits its invars into
    cond-consts + body-consts + carry; ``cond`` prefixes the predicate;
    everything else (pjit/scan/shard_map/remat/custom_* calls) aligns its
    sub invars to the TAIL of the equation invars (1:1 when lengths match)."""
    name = eqn.primitive.name
    if name == "while":
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        body = eqn.params["body_jaxpr"]
        body = body.jaxpr if isinstance(body, jcore.ClosedJaxpr) else body
        if sub is body:
            return eqn.invars[cn + idx]
        return eqn.invars[idx] if idx < cn else eqn.invars[bn + idx]
    if name == "cond":
        return eqn.invars[idx + 1]
    n_in, n_sub = len(eqn.invars), len(sub.invars)
    if n_sub <= n_in:
        return eqn.invars[n_in - n_sub + idx]
    return None


def _call_outvar_sources(prod, pos, jaxpr, frames):
    """Where a call-like producer's ``pos``-th output comes from: the
    matching sub-jaxpr outvar (descending a frame), plus — for ``while`` —
    the initial carry operand (the loop may pass the value through
    untouched)."""
    name = prod.primitive.name
    inner = frames + ((jaxpr, prod),)
    if name == "while":
        body = prod.params["body_jaxpr"]
        body = body.jaxpr if isinstance(body, jcore.ClosedJaxpr) else body
        cn = int(prod.params.get("cond_nconsts", 0))
        bn = int(prod.params.get("body_nconsts", 0))
        if pos < len(body.outvars):
            yield body.outvars[pos], body, inner
        if cn + bn + pos < len(prod.invars):
            yield prod.invars[cn + bn + pos], jaxpr, frames
        return
    if name == "cond":
        for br in prod.params.get("branches", ()):
            br = br.jaxpr if isinstance(br, jcore.ClosedJaxpr) else br
            if pos < len(br.outvars):
                yield br.outvars[pos], br, inner
        return
    for sub in sub_jaxprs(prod):
        if pos < len(sub.outvars):
            yield sub.outvars[pos], sub, inner


def traces_to_mask(var, jaxpr, frames, cache=None, seen=None) -> bool:
    """Does ``var``'s producer chain contain a ``select_n`` mask gate?

    Walks backward through ``MASK_PASS_THROUGH`` primitives and through
    ``pallas_call`` pack kernels (an all-zero vote block packs to all-zero
    wire bytes). A jaxpr invar maps UP to the calling equation's operand
    (``frames`` is the ((jaxpr, eqn), ...) call stack built by the site
    walker); a call-like producer maps DOWN into its sub-jaxpr's matching
    outvar. Cycles (the while carry) are cut by the visited set.
    """
    cache = {} if cache is None else cache
    seen = set() if seen is None else seen
    if isinstance(var, jcore.Literal):
        return False
    key = (id(jaxpr), id(var))
    if key in seen:
        return False
    seen.add(key)
    prod = _producers(jaxpr, cache).get(id(var))
    if prod is None:
        # a jaxpr invar: continue in the caller's scope. constvars (closed-
        # over constants) are never mask outputs — dead end.
        try:
            idx = jaxpr.invars.index(var)
        except ValueError:
            return False
        if not frames:
            return False
        caller_jaxpr, caller_eqn = frames[-1]
        outer = _map_invar_out(caller_eqn, jaxpr, idx)
        if outer is None:
            return False
        return traces_to_mask(outer, caller_jaxpr, frames[:-1], cache, seen)
    name = prod.primitive.name
    if name == "select_n":
        return True
    if name == "pallas_call" or name in MASK_PASS_THROUGH:
        return any(traces_to_mask(v, jaxpr, frames, cache, seen)
                   for v in prod.invars if not isinstance(v, jcore.Literal))
    try:
        pos = prod.outvars.index(var)
    except ValueError:
        return False
    for src_var, src_jaxpr, src_frames in _call_outvar_sources(
            prod, pos, jaxpr, frames):
        if traces_to_mask(src_var, src_jaxpr, src_frames, cache, seen):
            return True
    return False


def _gather_payload_sites(jaxpr, frames, out):
    """Collect (eqn, operand var, owning jaxpr, frames) for every untiled
    gather of a >= 2-element integer payload, descending like the census
    walker (pallas bodies excluded) with the call stack threaded through."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if (name in GATHER_PRIMS and not eqn.params.get("tiled", False)
                and _named_axes(eqn)):
            for v in eqn.invars:
                if (not isinstance(v, jcore.Literal)
                        and _is_int_payload(getattr(v, "aval", None))):
                    out.append((eqn, v, jaxpr, frames))
        if name == "pallas_call":
            continue
        for sub in sub_jaxprs(eqn):
            _gather_payload_sites(sub, frames + ((jaxpr, eqn),), out)


class MaskedPayloadZero(Rule):
    """Every gather-wire payload must carry its participation mask gate.

    SPMD ships fixed shapes, so a masked-out (non-reporting) worker's bytes
    still ride every gather wire — correctness of the vote demands those
    bytes be EXACT zeros (an all-zero packed message decodes to zero votes;
    stale nonzero bytes would vote). The structural witness is a
    ``select_n`` — ``VoteWire.mask_message``'s ``jnp.where`` — somewhere in
    the gathered operand's producer chain. The rule backtracks every
    untiled >= 2-element integer-dtype ``all_gather``/``ppermute`` operand
    (packed payloads are integer buffers; the f32 scale/weight side
    channels legitimately ship values and are exempt) through
    shape-preserving primitives, across while/scan/pjit scope boundaries,
    and through pallas pack kernels — and blocks when no mask gate is
    found. FSDP parameter movement (``tiled=True``) is exempt: parameters
    are replicated state, not per-worker reports.
    """

    name = "masked-payload-zero"
    description = ("untiled gather payloads must trace back to a "
                   "participation mask (select_n)")

    def check(self, label: str, fn, *args) -> list:
        sites: list = []
        _gather_payload_sites(_as_jaxpr(fn, args), (), sites)
        findings, cache = [], {}
        for eqn, var, owner, frames in sites:
            if traces_to_mask(var, owner, frames, cache):
                continue
            aval = var.aval
            findings.append(self.finding(
                label,
                f"untiled {eqn.primitive.name}[{','.join(_named_axes(eqn))}] "
                f"ships a {jnp.dtype(aval.dtype).name}{tuple(aval.shape)} "
                f"payload with no participation mask (select_n) in its "
                f"producer chain — a non-reporting worker's stale bytes "
                f"would ride the wire and vote"))
        return findings


# ---------------------------------------------------------------------------
# DtypePromotionDrift — f32 leaks on declared-narrow leaf paths
# ---------------------------------------------------------------------------

class DtypePromotionDrift(Rule):
    """No >= min_elems tensor of a banned (wide) dtype may hit HBM on a path
    declared narrow — e.g. a bf16 gradient leaf reaching the packed wire must
    not round-trip through a full-size f32 copy (in-register f32 math inside
    kernel bodies is fine and expected)."""

    name = "dtype-promotion-drift"
    description = "no full-size wide-dtype HBM tensors on narrow leaf paths"

    def __init__(self, banned: Sequence = ("float32",), min_elems: int = 2):
        self.banned = tuple(jnp.dtype(d) for d in banned)
        self.min_elems = int(min_elems)

    def check(self, label: str, fn, *args) -> list:
        leaks: Counter = Counter()
        for eqn in iter_eqns(_as_jaxpr(fn, args)):
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt in self.banned and math.prod(aval.shape) >= self.min_elems:
                    leaks[(eqn.primitive.name, dt.name)] += math.prod(aval.shape)
        if not leaks:
            return []
        worst = ", ".join(f"{prim}->{dt}({n})" for (prim, dt), n
                          in leaks.most_common(3))
        return [self.finding(
            label,
            f"{sum(leaks.values())} wide-dtype elements (>= {self.min_elems} "
            f"per tensor) materialized on a declared-narrow path: {worst}")]
