"""Shared plumbing for the compression kernels.

All kernels operate on a canonical 2D layout: the caller's tensor is flattened
row-major and viewed as (rows, LANES) with LANES a multiple of 128 (TPU lane
width) and rows padded to the sublane tile of the widest dtype in play
(int8 tiles are (32, 128), f32 tiles are (8, 128) — we pad rows to 32-multiples
so one BlockSpec serves mixed-dtype kernels).

The logical coordinate of element (r, c) is ``r * LANES + c`` — identical to its
index in the caller's flat tensor — so the counter-based RNG stream is invariant
to this packing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 512            # lane-dim width of the canonical view (4 * 128)
SUBLANE_PAD = 32       # row padding multiple (int8 sublane tile)
DEFAULT_BLOCK_ROWS = 256

# murmur3 finalizer constants as numpy scalars (NOT jnp arrays) so they inline
# as literals inside Pallas kernel bodies. One copy shared by every kernel
# that regenerates the counter stream; must mirror repro.core.prng exactly —
# tests pin kernel == prng-based oracle bitwise.
RNG_C1 = np.uint32(0x85EBCA6B)
RNG_C2 = np.uint32(0xC2B2AE35)
RNG_GOLDEN = np.uint32(0x9E3779B9)


def mix32(x):
    """murmur3 fmix32 over uint32 values, kernel-inlinable (literal constants).
    The in-kernel twin of ``repro.core.prng.mix32``."""
    x = x ^ (x >> 16)
    x = x * RNG_C1
    x = x ^ (x >> 13)
    x = x * RNG_C2
    x = x ^ (x >> 16)
    return x


def uniform24(bits):
    """Top 24 bits of a uint32 hash as a float32 uniform in [0, 1).

    The shift leaves a value below 2**24, which int32 and float32 both hold
    exactly; the detour through int32 is there because Mosaic has no
    uint32 -> float32 conversion."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def pack2bit_quads(t, quarter: int):
    """(rows, 4*quarter) ternary {-1,0,1} -> (rows, quarter) uint8 in the
    pack2bit wire codebook (0 -> 00, +1 -> 01, -1 -> 10): byte j packs the
    codes of lane columns (j, j+q, j+2q, j+3q), the block-interleaved layout
    of pack2bit/ref.py. Shared by the pack and fused compress+pack kernels.

    Codes are built in int32 and narrowed once at the end: the TPU vector
    unit has no 8-bit compares or shifts."""
    t = t.astype(jnp.int32)
    c = [jnp.where(q < 0, jnp.int32(2), q)
         for q in (t[:, k * quarter:(k + 1) * quarter] for k in range(4))]
    return (c[0] | (c[1] << 2) | (c[2] << 4) | (c[3] << 6)).astype(jnp.uint8)


def decode2bit(codes, k: int):
    """The k-th 2-bit field of int32 packed bytes -> vote in {-1, 0, 1}
    (int32): code 1 -> +1, code 2 -> -1, 0 -> 0. Arithmetic, not a select,
    so no boolean mask is relaid out across the worker axis."""
    c = (codes >> (2 * k)) & 3
    return (c & 1) - (c >> 1)


def default_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode everywhere except real TPUs."""
    return jax.default_backend() != "tpu"


def canonical_rows(n: int, lanes: int = LANES, row_pad: int = SUBLANE_PAD) -> int:
    """Row count of the canonical (rows, lanes) view of an n-element stream:
    ceil to full lanes, rows padded to the sublane tile. The single source of
    the padding rule — ``to_2d`` builds the buffers with it and the wire
    ledgers (``dist.collectives.packed_nbytes``/``packed8_nbytes``) size the
    real payloads from it, so accounting can never drift from the buffers."""
    rows = -(-n // lanes)
    return -(-rows // row_pad) * row_pad


def to_2d(flat: jnp.ndarray, lanes: int = LANES, row_pad: int = SUBLANE_PAD):
    """Pad a flat array to a (rows, lanes) canonical view.

    Returns (view, original_size). Padding is zeros (harmless for every kernel
    here: sign(0)=0, votes 0, pack of 0 is 0).
    """
    assert flat.ndim == 1
    n = flat.shape[0]
    rows = canonical_rows(n, lanes, row_pad)
    padded = jnp.zeros((rows * lanes,), dtype=flat.dtype).at[:n].set(flat)
    return padded.reshape(rows, lanes), n


def from_2d(view: jnp.ndarray, n: int, shape, dtype=None):
    out = view.reshape(-1)[:n].reshape(shape)
    return out.astype(dtype) if dtype is not None else out


def block_rows_for(rows: int, want: int = DEFAULT_BLOCK_ROWS) -> int:
    """Largest divisor of ``rows`` that is <= want and a multiple of SUBLANE_PAD."""
    want = min(want, rows)
    want = max(SUBLANE_PAD, (want // SUBLANE_PAD) * SUBLANE_PAD)
    while rows % want:
        want -= SUBLANE_PAD
    return max(want, SUBLANE_PAD)


def gather_block_rows(gathered_shape) -> int:
    """Block rows of a kernel that reads an (M, rows, q) gathered payload:
    the (M, block, q) input block stays within about 2 MiB of VMEM at any
    worker count, so the block shrinks as M grows."""
    m, rows, q = gathered_shape
    want = max(SUBLANE_PAD, min(DEFAULT_BLOCK_ROWS, (1 << 21) // max(1, m * q)))
    return block_rows_for(rows, want=want)


def smem_row(dtype, *xs) -> jnp.ndarray:
    """Scalars ride in SMEM as one (1, k) row per dtype. Float scalars get a
    float32 row of their own: Mosaic cannot bitcast an SMEM scalar."""
    return jnp.stack([jnp.asarray(x, dtype) for x in xs]).reshape(1, len(xs))


def hbm_elems(fn, *args, dtype=jnp.int8) -> int:
    """Element count of ``dtype`` arrays materialized *between* ops when
    tracing ``fn(*args)`` — i.e. HBM-level traffic of that dtype. The walker
    lives in ``repro.analysis.jaxpr_audit`` (recursive over every sub-jaxpr,
    including custom_jvp/custom_vjp/closed_call bodies, but never descending
    into a pallas_call's kernel body, whose values live in VMEM registers);
    this shim keeps the kernels' historical entry point. Used by the wire
    tests/bench to pin that the fused uplinks have no int8 ternary (2-bit
    wire) or int32 level (pack8 wire) intermediate while the unfused chains
    necessarily do."""
    from repro.analysis import jaxpr_audit  # lazy: analysis imports kernels

    return jaxpr_audit.hbm_elems(fn, *args, dtype=dtype)


def int8_hbm_elems(fn, *args) -> int:
    """HBM-level int8 element count of ``fn(*args)`` (see ``hbm_elems``)."""
    return hbm_elems(fn, *args, dtype=jnp.int8)


def int32_hbm_elems(fn, *args) -> int:
    """HBM-level int32 element count of ``fn(*args)`` (see ``hbm_elems``)."""
    return hbm_elems(fn, *args, dtype=jnp.int32)


@functools.lru_cache(maxsize=None)
def vmem_bytes(block_rows: int, lanes: int, *dtypes) -> int:
    per = {jnp.float32.dtype: 4, jnp.bfloat16.dtype: 2, jnp.int8.dtype: 1,
           jnp.uint8.dtype: 1, jnp.int32.dtype: 4, jnp.uint32.dtype: 4}
    return sum(block_rows * lanes * per[jnp.dtype(d)] for d in dtypes)
