"""Distribution-infrastructure unit tests: HLO collective parser, placement
sanitizer, wire models, logical-axis specs, dry-run helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks.bench_collectives import wire_model
from benchmarks.bench_roofline import analytic_cell
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, all_cells
from repro.dist.sharding import logical_to_spec, sanitize_spec
from repro.launch import hlo_stats

HLO_SAMPLE = """
  %ar = f32[1024,256]{1,0} all-reduce(%x), replica_groups=[32,16]<=[512], to_apply=%add
  %ag.1 = bf16[64,4096]{1,0} all-gather(%y), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %rs = f32[16,16]{1,0} reduce-scatter(%z), replica_groups=[4,4]<=[16], dimensions={0}
  %cp = u8[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %unrelated = f32[8]{0} add(%a, %b)
"""


def test_hlo_parser_counts_and_bytes():
    stats = hlo_stats.parse_collectives(HLO_SAMPLE)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1,
                            "reduce-scatter": 1, "collective-permute": 1}
    # all-reduce: 2*(15/16)*1024*256*4 bytes
    ar = stats.bytes_by_op["all-reduce"]
    assert abs(ar - 2 * 15 / 16 * 1024 * 256 * 4) < 1.0
    # all-gather group of 4: (3/4) * 64*4096*2
    ag = stats.bytes_by_op["all-gather"]
    assert abs(ag - 0.75 * 64 * 4096 * 2) < 1.0
    assert stats.wire_bytes > 0


def test_hlo_parser_group_formats():
    assert hlo_stats._group_size("replica_groups=[32,16]<=[512]", 2) == 16
    assert hlo_stats._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}", 2) == 4
    assert hlo_stats._group_size("no groups here", 7) == 7


@pytest.fixture(scope="module")
def mesh16():
    # abstract-shaped mesh over 1 device is fine for spec math only
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_sanitize_spec_nulls_nondividing(mesh16):
    # vocab 50280 not divisible by 16 -> replicated; 8192 is -> kept
    s = sanitize_spec(P("model", None), (50280, 1024), mesh16)
    assert s == P(None, None)
    s2 = sanitize_spec(P("model", None), (8192, 1024), mesh16)
    assert s2 == P("model", None)
    # tuple axes: ('data','model') = 256 must divide
    s3 = sanitize_spec(P(("data", "model")), (512,), mesh16)
    assert s3 == P(("data", "model"))
    s4 = sanitize_spec(P(("data", "model")), (128,), mesh16)
    assert s4 == P(None)


def test_logical_to_spec_rules():
    assert logical_to_spec(("vocab", None)) == P("model", None)
    assert logical_to_spec((None, "heads")) == P(None, "model")
    assert logical_to_spec(("expert", None, "ff")) == P("model", None, "model")


def test_wire_model_orderings():
    n = 10_000_000
    fp32 = wire_model(n, "simple", variant="fp32_dp")["grad_exchange"]
    int8 = wire_model(n, "simple", variant="sparsign_int8")["grad_exchange"]
    assert abs(fp32 / int8 - 4.0) < 0.01
    st = wire_model(n, "streamed", variant="sparsign_int8")
    assert st["fsdp_gather"] > 0 and st["total"] > st["grad_exchange"]


def test_analytic_cell_sanity():
    """Roofline terms positive/finite; decode compute << train compute;
    windowed gemma long-decode cheaper than a hypothetical full-window one."""
    for arch in ("gemma3-27b", "qwen2-moe-a2.7b"):
        tr = analytic_cell(arch, "train_4k", "16x16", "simple")
        de = analytic_cell(arch, "decode_32k", "16x16", "simple")
        for t in (tr, de):
            assert all(np.isfinite(v) and v >= 0 for k, v in t.items() if k.endswith("_s"))
        assert de["compute_s"] < tr["compute_s"] / 100
    g_long = analytic_cell("gemma3-27b", "long_500k", "16x16", "simple")
    assert g_long["memory_s"] < 0.05  # ring caches keep 500k decode cheap


def test_cells_inventory_is_40():
    """10 archs x 4 shapes; skips documented with reasons."""
    rows = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        rows.extend((arch, s.name, runs, why) for s, runs, why in all_cells(cfg))
    assert len(rows) == 40
    skips = [r for r in rows if not r[2]]
    assert len(skips) == 8
    assert all(r[3] for r in skips), "every skip carries a reason"


def test_shapes_definition():
    assert SHAPES["train_4k"].kind == "train"
    assert SHAPES["long_500k"].global_batch == 1
    assert SHAPES["decode_32k"].seq_len == 32768
