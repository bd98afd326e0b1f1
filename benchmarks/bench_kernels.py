"""Compression-kernel counts: jnp reference vs Pallas for the three engine
kernels (sparsign, vote_update, ef_server) plus the pack2bit wire packer, at
model-realistic leaf shapes.

Each record carries the structural TPU memory-traffic model
(``hbm_bytes_per_coord_tpu``) and, for the uplink chains, the int8/int32
tensors they leave in HBM, counted from the traced programs. Nothing is
timed: the kernels' device time comes from the chip benchmark (``bench/``).
Full runs write ``BENCH_kernels.json`` at the
repo root (the tracked bench-trajectory baseline); ``--quick`` writes
``BENCH_kernels.quick.json`` (the CI smoke artifact) so it can't clobber the
baseline.

  python -m benchmarks.bench_kernels            # full shapes
  python -m benchmarks.bench_kernels --quick    # CI smoke
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_header, csv_row
from repro.analysis.jaxpr_audit import NoHbmIntermediate
from repro.kernels import common as kcommon
from repro.kernels.golomb.ops import golomb_pack_op, sparsign_golomb_op
from repro.kernels.golomb.ref import golomb_encode_ref, golomb_nbytes
from repro.kernels.pack2bit.ops import pack2bit_op
from repro.kernels.pack2bit.ref import pack2bit_ref
from repro.kernels.pack8.ops import qsgd8_op, qsgd8_pack8_op
from repro.kernels.sparsign.ops import sparsign_op
from repro.kernels.sparsign.ref import sparsign_ref
from repro.kernels.sparsign_pack2bit.ops import sparsign_pack2bit_op
from repro.kernels.ternary.ops import ternary_compress_op, ternary_pack2bit_op

ROOT = Path(__file__).resolve().parents[1]
OUT_PATH = ROOT / "BENCH_kernels.json"            # tracked full-shape baseline
QUICK_OUT_PATH = ROOT / "BENCH_kernels.quick.json"  # CI smoke; never tracked

# model-realistic leaf shapes (qwen1.5-4b-class: hidden 2560, ffn 6912;
# embed shard = vocab slice of an FSDP-sharded embedding table)
SHAPES_FULL = {
    "attn_proj_2560x2560": (2560, 2560),
    "mlp_up_2560x6912": (2560, 6912),
    "embed_shard_8192x2560": (8192, 2560),
}
SHAPES_QUICK = {
    "leaf_64k": (512, 128),
    "leaf_256k": (512, 512),
}

# TPU HBM traffic per coordinate (structural, independent of where we time)
BYTES_PER_COORD = {
    ("sparsign", "pallas"): 4 + 1,        # read f32, write i8; RNG in-register
    ("sparsign", "jnp"): 4 + 4 + 4 + 1,   # + u32 idx and f32 uniform traffic
    ("vote_update", "pallas"): 4 + 4 + 4, # w + votes -> w' in one pass
    ("vote_update", "jnp"): 4 * 4,        # sign/cast/scale/sub ~4 passes
    ("ef_server", "pallas"): 8 + 8,       # (d,e) in, (out,e') out fused
    ("ef_server", "jnp"): 8 * 3,          # ~4-pass unfused chain over (d,e)
    ("pack2bit", "pallas"): 1 + 0.25,
    # the allgather_packed uplink, fused vs two-pass: fused reads the f32
    # gradient and writes wire bytes in ONE kernel (the int8 ternary tensor
    # never exists in HBM); two-pass pays the compress write + pack read
    ("uplink_fused", "pallas"): 4 + 0.25,
    ("uplink_two_pass", "pallas"): (4 + 1) + (1 + 0.25),
    ("uplink_two_pass", "jnp"): (4 + 4 + 4 + 1) + (1 + 0.25),
    # the generic ternary template's fused uplinks (CompressorSpec registry):
    # same single-pass structure for every ternary compressor — noisy_sign
    # draws two RNG streams (both in-register, zero extra HBM traffic),
    # terngrad's s_t arrives as a pre-reduced scalar in SMEM
    ("uplink_fused_noisy_sign", "pallas"): 4 + 0.25,
    ("uplink_fused_terngrad", "pallas"): 4 + 0.25,
    ("uplink_two_pass_noisy_sign", "pallas"): (4 + 1) + (1 + 0.25),
    ("uplink_two_pass_terngrad", "pallas"): (4 + 1) + (1 + 0.25),
    # the entropy-coded (golomb) uplink at plan p=0.05: fused reads the f32
    # gradient and writes the coded byte stream in ONE pass (~0.05 B/coord of
    # capacity rows on the wire — sub-2-bit); two-pass pays the int8 ternary
    # write + re-read before coding
    ("uplink_fused_golomb", "pallas"): 4 + 0.05,
    ("uplink_two_pass_golomb", "pallas"): (4 + 1) + (1 + 0.05),
    ("uplink_two_pass_golomb", "jnp"): (4 + 4 + 4 + 1) + (1 + 0.05),
    # the 8-bit QSGD (pack8) uplink: fused reads the f32 gradient and writes
    # the int8 sign*level wire payload in ONE pass (1 B/coord on the wire);
    # the decoded-psum chain it replaces quantizes, re-reads the levels and
    # writes the 4 B/coord fp32 psum payload
    ("uplink_fused_qsgd8", "pallas"): 4 + 1,
    ("uplink_decoded_psum_qsgd8", "pallas"): (4 + 1) + (1 + 4),
    ("uplink_decoded_psum_qsgd8", "jnp"): (4 + 4 + 4 + 1) + (1 + 4),
}


def _bench_shape(name: str, shape, records: list, pallas_label: str):
    n = int(np.prod(shape))
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(*shape), jnp.float32)
    t = jnp.asarray(rng.randint(-1, 2, shape), jnp.int8)

    # all-jnp two-pass uplink (what the engine's jnp backend runs for the
    # packed wire): reference compress + reference pack over the canonical view
    uplink_jnp = jax.jit(lambda x: pack2bit_ref(
        kcommon.to_2d(sparsign_ref(x, 1.0, 7).reshape(-1))[0]))

    cases = [("sparsign", "pallas"), ("sparsign", "jnp"),
             ("vote_update", "pallas"), ("vote_update", "jnp"),
             ("ef_server", "pallas"), ("ef_server", "jnp"),
             ("pack2bit", "pallas"), ("uplink_fused", "pallas"),
             ("uplink_two_pass", "pallas"), ("uplink_two_pass", "jnp")]
    # the generic ternary template's fused uplinks (noisy_sign sigma=0.01 as
    # Appendix B tunes it; terngrad against its local L-inf normalizer) — one
    # tuple drives both the records and the int8-HBM assertions below
    s_t = float(np.max(np.abs(np.asarray(g))))
    ternary_uplinks = (("noisy_sign", "noisy_sign", 0.01),
                       ("terngrad", "stochastic_ternary", s_t))
    for label, _, _ in ternary_uplinks:
        cases += [(f"uplink_fused_{label}", "pallas"),
                  (f"uplink_two_pass_{label}", "pallas")]
    # the entropy-coded golomb uplink (sparsign at ~5% realized density vs a
    # plan capacity of p=0.05): fused gradient->coded-bytes kernel vs the
    # two-pass compress-then-encode chain, plus the engine's all-jnp reference
    # (sparsign_ref + the format-defining reference coder)
    p_g, budget_g = 0.05, 0.06
    golomb_jnp = jax.jit(lambda x: golomb_encode_ref(
        sparsign_ref(x, budget_g, 7), p=p_g))
    cases += [("uplink_fused_golomb", "pallas"),
              ("uplink_two_pass_golomb", "pallas"),
              ("uplink_two_pass_golomb", "jnp")]
    # the 8-bit QSGD (pack8) uplink vs the decoded-psum chain it replaces
    # (1 B/coord wire payload vs 4 B/coord fp32); seed passed as uint32 like
    # the engine supplies it, so the no-int32 jaxpr pin below stays exact
    s8 = max(float(np.linalg.norm(np.asarray(g))), 1e-12) / 127.0
    seed8 = jnp.uint32(7)
    cases += [("uplink_fused_qsgd8", "pallas"),
              ("uplink_decoded_psum_qsgd8", "pallas"),
              ("uplink_decoded_psum_qsgd8", "jnp")]
    # structural guarantee behind the fused uplinks' byte count: no int8
    # ternary tensor at the HBM level (the two-pass chains have one of >= n),
    # measured per backend on the exact chains recorded above.  The "no
    # intermediate" side is the declarative NoHbmIntermediate rule from
    # repro.analysis (same rule CI's `python -m repro.analysis` gate runs per
    # CompressorSpec); the two-pass counts stay numeric for the JSON records.
    no_i8 = NoHbmIntermediate(jnp.int8)
    findings = no_i8.check(
        "uplink_fused", lambda x: sparsign_pack2bit_op(x, 1.0, 7), g)
    assert findings == [], "\n".join(f.render() for f in findings)
    two_pass_i8 = kcommon.int8_hbm_elems(
        lambda x: pack2bit_op(sparsign_op(x, 1.0, 7)), g)
    two_pass_jnp_i8 = kcommon.int8_hbm_elems(uplink_jnp, g)
    assert two_pass_i8 >= n and two_pass_jnp_i8 >= n
    int8_hbm = {("uplink_fused", "pallas"): 0,
                ("uplink_two_pass", "pallas"): two_pass_i8,
                ("uplink_two_pass", "jnp"): two_pass_jnp_i8}
    for label, rule, param in ternary_uplinks:
        findings = no_i8.check(
            f"uplink_fused_{label}",
            lambda x: ternary_pack2bit_op(x, param, 7, rule=rule), g)
        assert findings == [], "\n".join(f.render() for f in findings)
        t_i8 = kcommon.int8_hbm_elems(
            lambda x: pack2bit_op(ternary_compress_op(x, param, 7, rule=rule)), g)
        assert t_i8 >= n
        int8_hbm[(f"uplink_fused_{label}", "pallas")] = 0
        int8_hbm[(f"uplink_two_pass_{label}", "pallas")] = t_i8
    # golomb structural pin: the fused coded uplink never materializes the
    # int8 ternary tensor (both two-pass chains do, >= n elements) — and its
    # payload really is the sub-2-bit capacity buffer the ledger bills
    findings = no_i8.check(
        "uplink_fused_golomb",
        lambda x: sparsign_golomb_op(x, budget_g, 7, p=p_g), g)
    assert findings == [], "\n".join(f.render() for f in findings)
    gp_i8 = kcommon.int8_hbm_elems(
        lambda x: golomb_pack_op(sparsign_op(x, budget_g, 7), p=p_g), g)
    gj_i8 = kcommon.int8_hbm_elems(golomb_jnp, g)
    assert gp_i8 >= n and gj_i8 >= n
    assert sparsign_golomb_op(g, budget_g, 7, p=p_g).nbytes \
        == golomb_nbytes(n, p_g) < pack2bit_op(t).nbytes
    int8_hbm[("uplink_fused_golomb", "pallas")] = 0
    int8_hbm[("uplink_two_pass_golomb", "pallas")] = gp_i8
    int8_hbm[("uplink_two_pass_golomb", "jnp")] = gj_i8
    # pack8 structural pin: the fused qsgd8 uplink has no int32 level tensor
    # at the HBM level (limit=1 allows the to_2d pad's scatter-start index,
    # exactly qsgd8's declared hbm_limits); the decoded chain necessarily
    # re-reads its int8 levels for the f32 decode
    findings = NoHbmIntermediate(jnp.int32, limit=1).check(
        "uplink_fused_qsgd8", lambda x: qsgd8_pack8_op(x, s8, seed8), g)
    assert findings == [], "\n".join(f.render() for f in findings)
    f8_i32 = kcommon.int32_hbm_elems(lambda x: qsgd8_pack8_op(x, s8, seed8), g)
    d8_i8 = kcommon.int8_hbm_elems(
        lambda x: qsgd8_op(x, s8, seed8).astype(jnp.float32)
        * jnp.float32(s8), g)
    assert d8_i8 >= n
    int32_hbm = {("uplink_fused_qsgd8", "pallas"): f8_i32}
    int8_hbm[("uplink_decoded_psum_qsgd8", "pallas")] = d8_i8

    for kernel, backend in cases:
        label = pallas_label if backend == "pallas" else "jnp"
        rec = {
            "kernel": kernel,
            "shape": name,
            "dims": list(shape),
            "n_coords": n,
            "backend": label,
            "hbm_bytes_per_coord_tpu": BYTES_PER_COORD.get((kernel, backend)),
        }
        if (kernel, backend) in int8_hbm:
            rec["int8_hbm_intermediate_elems"] = int8_hbm[(kernel, backend)]
        if (kernel, backend) in int32_hbm:
            rec["int32_hbm_intermediate_elems"] = int32_hbm[(kernel, backend)]
        records.append(rec)
        csv_row([kernel, name, label, rec["hbm_bytes_per_coord_tpu"]])


def main(fast: bool = False, out: Path | None = None):
    shapes = SHAPES_QUICK if fast else SHAPES_FULL
    on_tpu = jax.default_backend() == "tpu"
    pallas_label = "pallas" if on_tpu else "pallas-interpret"
    print(f"# kernel counts: jnp vs {pallas_label} "
          f"(jax backend={jax.default_backend()})")
    csv_header(["kernel", "shape", "backend", "hbm_bytes_per_coord_tpu"])
    records: list[dict] = []
    for name, shape in shapes.items():
        _bench_shape(name, shape, records, pallas_label)

    doc = {
        "schema": 1,
        "bench": "kernels",
        "jax_backend": jax.default_backend(),
        "pallas_mode": "compiled" if on_tpu else "interpret",
        "jax_version": jax.__version__,
        "quick": fast,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": ("hbm_bytes_per_coord_tpu is the structural TPU traffic model "
                 "behind the roofline term; nothing here is timed."),
        "results": records,
    }
    # quick runs get their own default path so a CI-smoke invocation can't
    # silently clobber the committed full-shape baseline
    out = out or (QUICK_OUT_PATH if fast else OUT_PATH)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"# wrote {out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args()
    main(fast=args.quick, out=args.out)
