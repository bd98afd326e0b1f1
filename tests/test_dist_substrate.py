"""Substrate tests for repro.dist beyond the seed suite: sanitize_spec edge
cases, whole-tree placement builders, and the vote-collective equivalence
(subprocess-forced 8-device host mesh, pattern of tests/mdev/)."""

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import run_mdev

from repro.dist.sharding import (ACT_RULES_SERVE, ACT_RULES_TRAIN, TP_RULES,
                                 cache_shardings_tree, logical_to_spec,
                                 sanitize_spec, tp_param_shardings)

@pytest.fixture(scope="module")
def mesh16():
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


# ---------------------------------------------------------------------------
# sanitize_spec edge cases
# ---------------------------------------------------------------------------

def test_sanitize_zero_dim_replicates(mesh16):
    assert sanitize_spec(P("model"), (0,), mesh16) == P(None)


def test_sanitize_size_one_axis_kept():
    m = jax.sharding.AbstractMesh((1, 16), ("data", "model"))
    # a size-1 mesh axis divides everything: placement kept (it's a no-op)
    assert sanitize_spec(P("data", "model"), (7, 32), m) == P("data", "model")


def test_sanitize_repeated_mesh_axis_last_wins(mesh16):
    # 'model' claimed by dims 0 and 2 (the raw expert x .. x ff spec): the
    # LAST occurrence keeps it, matching hint()'s convention
    s = sanitize_spec(P("model", None, "model"), (64, 32, 128), mesh16)
    assert s == P(None, None, "model")
    # ...unless the last one fails divisibility — then the earlier survives
    s2 = sanitize_spec(P("model", None, "model"), (64, 32, 100), mesh16)
    assert s2 == P("model", None, None)


def test_sanitize_repeat_inside_tuple_nulls_dim(mesh16):
    assert sanitize_spec(P(("data", "data")), (512,), mesh16) == P(None)


def test_sanitize_tuple_scalar_overlap(mesh16):
    # 'model' inside a tuple on dim 0 and scalar on dim 1: last wins, the
    # whole earlier tuple entry is dropped (partial placements never survive)
    s = sanitize_spec(P(("data", "model"), "model"), (256, 64), mesh16)
    assert s == P(None, "model")


def test_sanitize_spec_shorter_than_dims(mesh16):
    assert sanitize_spec(P("model"), (32, 64, 128), mesh16) == P("model", None, None)


# ---------------------------------------------------------------------------
# rule tables / logical mapping
# ---------------------------------------------------------------------------

def test_rule_tables_cover_model_logical_axes():
    for name in ("vocab", "heads", "ff", "expert"):
        assert TP_RULES[name] == "model"
        assert ACT_RULES_TRAIN[name] == "model"
        assert ACT_RULES_SERVE[name] == "model"
    assert ACT_RULES_TRAIN["batch"] == "data"


def test_logical_to_spec_custom_rules():
    assert logical_to_spec(("batch", "seq"), ACT_RULES_SERVE) == P("data", None)


# ---------------------------------------------------------------------------
# whole-tree placement builders (1x1 host mesh: spec math, no multi-device)
# ---------------------------------------------------------------------------

def test_tp_param_shardings_tree(host_mesh11):
    from repro.configs.registry import get_config
    from repro.models.model import Model
    model = Model(get_config("qwen1.5-4b", smoke=True))
    sh = tp_param_shardings(model, host_mesh11)
    shapes = model.param_shapes()
    flat_sh = jax.tree_util.tree_leaves(sh)
    assert flat_sh and all(isinstance(s, NamedSharding) for s in flat_sh)
    assert (jax.tree_util.tree_structure(sh)
            == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, shapes)))
    # embed is vocab x d_model -> P('model', None) sanitized against real dims
    assert sh["embed"].spec[0] in ("model", None)


def test_cache_shardings_tree_layouts(host_mesh11):
    from repro.configs.registry import get_config
    from repro.models.model import Model
    model = Model(get_config("gemma3-27b", smoke=True))
    shapes = model.cache_shapes(batch_size=2, max_len=64)
    sh = cache_shardings_tree(shapes, host_mesh11, worker_axes=("data",))
    k = sh["body"][0]["k"]
    # stacked (r, b, w, kvh, hd): batch axis (1) carries the worker axis
    assert k.spec[1] in ("data", None) and len(k.spec) <= 5
    sh_seq = cache_shardings_tree(shapes, host_mesh11, worker_axes=("data",),
                                  shard_seq=True)
    k2 = sh_seq["body"][0]["k"].spec
    # shard_seq: batch replicated, the cache-depth axis takes the workers
    assert (len(k2) < 2 or k2[1] is None)
    assert (jax.tree_util.tree_structure(sh)
            == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, shapes)))


@pytest.fixture(scope="module")
def host_mesh11():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1)


# ---------------------------------------------------------------------------
# vote-collective equivalence (8-device subprocess)
# ---------------------------------------------------------------------------

def test_vote_collective_equivalence_8dev():
    out = run_mdev("check_collectives.py", timeout=600)
    assert "OK vote_psum == vote_allgather_packed == oracle" in out
    assert "OK vote_psum_hier == vote_psum == packed" in out
