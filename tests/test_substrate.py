"""Substrate tests: EF boundedness (Lemma 2), PRNG quality, encoding (Eq. 12),
checkpointing, data pipelines, worker sampling."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import prng
from repro.core.aggregation import alpha_of_scaled_sign, scaled_sign_server
from repro.core.encoding import (baseline_bits_per_round, golomb_bits_per_index,
                                 golomb_bstar, round_bits, ternary_stream_bits)
from repro.core.error_feedback import ef_server_step, init_ef
from repro.data.dirichlet import dirichlet_partition, heterogeneity_stats
from repro.data.synthetic import LMStreamConfig, lm_batch, make_image_dataset, ImageDataConfig
from repro.train import checkpoint as ckpt
from repro.train.sampling import participation_mask, round_seed
from repro.train.state import TrainState


# ---------------------------------------------------------------------------
# Error feedback (Lemma 2)
# ---------------------------------------------------------------------------

def test_ef_residual_bounded():
    """||e_t||^2 stays bounded over many rounds (Lemma 2)."""
    rng = np.random.RandomState(0)
    d = 2048
    state = init_ef(jnp.zeros(d))
    norms = []
    for t in range(200):
        delta = jnp.asarray(np.sign(rng.randn(d)) * rng.rand(d), jnp.float32)
        _, state = ef_server_step(state, delta)
        norms.append(float(jnp.sum(state.residual ** 2)))
    # bounded: the last 100 rounds don't grow
    assert max(norms[100:]) < 4.0 * max(norms[:100]) + 1e-6
    assert np.isfinite(norms[-1])


def test_scaled_sign_is_alpha_approximate():
    """||C(x) - x||^2 <= (1 - alpha) ||x||^2 with alpha = ||x||_1^2/(d ||x||_2^2)."""
    rng = np.random.RandomState(1)
    for _ in range(10):
        x = jnp.asarray(rng.randn(512) * rng.rand(), jnp.float32)
        cx = scaled_sign_server(x)
        alpha = float(alpha_of_scaled_sign(x))
        assert 0.0 < alpha <= 1.0 + 1e-6
        lhs = float(jnp.sum((cx - x) ** 2))
        rhs = (1.0 - alpha) * float(jnp.sum(x ** 2))
        assert lhs <= rhs + 1e-4


# ---------------------------------------------------------------------------
# PRNG quality
# ---------------------------------------------------------------------------

def test_prng_uniformity():
    u = np.asarray(prng.uniform01(123, jnp.arange(200000, dtype=jnp.uint32)))
    assert abs(u.mean() - 0.5) < 0.005
    assert abs(np.mean(u < 0.25) - 0.25) < 0.01
    # serial correlation
    assert abs(np.corrcoef(u[:-1], u[1:])[0, 1]) < 0.01


def test_prng_seed_independence():
    c = jnp.arange(100000, dtype=jnp.uint32)
    u1 = np.asarray(prng.uniform01(1, c))
    u2 = np.asarray(prng.uniform01(2, c))
    assert abs(np.corrcoef(u1, u2)[0, 1]) < 0.01


def test_fold_seed_distinct():
    seeds = {int(prng.fold_seed(42, i, j)) for i in range(20) for j in range(20)}
    assert len(seeds) == 400


# ---------------------------------------------------------------------------
# Encoding (Eq. 12)
# ---------------------------------------------------------------------------

def test_golomb_formula():
    # sparser streams need more bits per index; b* is nonnegative and monotone
    assert golomb_bstar(0.5) >= 0
    assert golomb_bstar(0.01) > golomb_bstar(0.2)
    assert golomb_bits_per_index(0.01) > golomb_bits_per_index(0.1) > golomb_bits_per_index(0.5)


@given(p=st.floats(0.001, 0.6))
@settings(max_examples=30, deadline=None)
def test_golomb_beats_naive_for_sparse(p):
    d = 100000
    nnz = max(1, int(p * d))
    g = ternary_stream_bits(d, nnz, coder="golomb")
    naive = ternary_stream_bits(d, nnz, coder="naive_index")
    assert g <= naive * 1.05


def test_round_bits_downlink_modes():
    d, nnz, m = 10000, 500, 100
    free = round_bits(d, nnz, m, downlink="free")
    sign = round_bits(d, nnz, m, downlink="sign")
    assert sign == free + d


def test_baseline_bits():
    d = 1000
    assert baseline_bits_per_round(d, "sign") == d
    assert baseline_bits_per_round(d, "identity") == 32 * d
    assert baseline_bits_per_round(d, "sparsign", nnz=100) < d  # sparser than 1 bit/coord
    # regression (PR 5): qsgd8 counts its 32-bit decode scale like the wire
    # ledger does (8 bits/coord + one f32 per message), and unknown algorithms
    # stay loud (no startswith("qsgd") catch-all)
    assert baseline_bits_per_round(d, "qsgd8") == 8 * d + 32
    with pytest.raises(ValueError):
        baseline_bits_per_round(d, "qsgd_777")


# ---------------------------------------------------------------------------
# Checkpointing / fault tolerance
# ---------------------------------------------------------------------------

def _tiny_state(seed=0):
    rng = np.random.RandomState(seed)
    return TrainState(
        params={"a": jnp.asarray(rng.randn(4, 8), jnp.float32),
                "b": (jnp.asarray(rng.randn(3), jnp.bfloat16),)},
        ef_residual=None,
        step=jnp.int32(7), seed=jnp.uint32(42))


def test_checkpoint_roundtrip(tmp_path):
    state = _tiny_state()
    ckpt.save(str(tmp_path), 7, state)
    restored, manifest = ckpt.restore(str(tmp_path), state)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_rotation_and_latest(tmp_path):
    state = _tiny_state()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, state, keep=2)
    assert ckpt.latest_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_structure_mismatch_rejected(tmp_path):
    state = _tiny_state()
    ckpt.save(str(tmp_path), 1, state)
    other = TrainState(params={"a": state.params["a"]}, ef_residual=None,
                       step=state.step, seed=state.seed)
    with pytest.raises(ckpt.CheckpointMismatchError, match="different model"):
        ckpt.restore(str(tmp_path), other)


def test_checkpoint_fingerprint_catches_shape_and_dtype_drift(tmp_path):
    """Same tree structure, different leaf shape/dtype -> loud mismatch (the
    stale-/tmp-checkpoint footgun: blind resume into another model config)."""
    state = _tiny_state()
    ckpt.save(str(tmp_path), 1, state)
    reshaped = TrainState(
        params={"a": jnp.zeros((8, 4), jnp.float32), "b": state.params["b"]},
        ef_residual=None, step=state.step, seed=state.seed)
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.restore(str(tmp_path), reshaped)
    retyped = TrainState(
        params={"a": state.params["a"].astype(jnp.bfloat16), "b": state.params["b"]},
        ef_residual=None, step=state.step, seed=state.seed)
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.restore(str(tmp_path), retyped)
    # matching state still round-trips, and the manifest carries the print
    restored, manifest = ckpt.restore(str(tmp_path), state)
    assert manifest["fingerprint"] == ckpt.tree_fingerprint(state)


def test_loop_skips_stale_checkpoint_with_warning(tmp_path):
    """train.loop must not blindly resume from a checkpoint another model
    config wrote into the same dir: it warns loudly and starts fresh."""
    from repro.train import loop as loop_lib
    stale = _tiny_state()
    ckpt.save(str(tmp_path), 5, stale)

    fresh = TrainState(params={"w": jnp.zeros((3, 3), jnp.float32)},
                       ef_residual=None, step=jnp.int32(0), seed=jnp.uint32(0))
    calls = []

    def fake_step(state, batch):
        calls.append(int(state.step))
        return TrainState(params=state.params, ef_residual=None,
                          step=state.step + 1, seed=state.seed), {"loss": jnp.float32(0.0)}

    logs = []
    cfg = loop_lib.LoopConfig(total_steps=2, ckpt_dir=str(tmp_path),
                              ckpt_every=0, log_every=1)
    out, history = loop_lib.run(fake_step, fresh, lambda i: {}, cfg,
                                log=logs.append)
    assert calls == [0, 1], calls                      # started fresh, not at 5
    assert any("WARNING" in line for line in logs), logs
    assert int(out.step) == 2


def test_loop_resumes_newest_compatible_past_stale_shadow(tmp_path):
    """A stale high-step checkpoint must not shadow this run's own valid
    checkpoints at lower steps: resume picks the newest COMPATIBLE one."""
    from repro.train import loop as loop_lib
    stale = _tiny_state()
    ckpt.save(str(tmp_path), 500, stale)      # foreign config, highest step

    own = TrainState(params={"w": jnp.ones((2, 2), jnp.float32)},
                     ef_residual=None, step=jnp.int32(30), seed=jnp.uint32(0))
    ckpt.save(str(tmp_path), 30, own)         # this run's real checkpoint

    calls = []

    def fake_step(state, batch):
        calls.append(int(state.step))
        return TrainState(params=state.params, ef_residual=None,
                          step=state.step + 1, seed=state.seed), {"loss": jnp.float32(0.0)}

    logs = []
    like = TrainState(params={"w": jnp.zeros((2, 2), jnp.float32)},
                      ef_residual=None, step=jnp.int32(0), seed=jnp.uint32(0))
    cfg = loop_lib.LoopConfig(total_steps=32, ckpt_dir=str(tmp_path),
                              ckpt_every=0, log_every=1)
    out, _ = loop_lib.run(fake_step, like, lambda i: {}, cfg, log=logs.append)
    assert calls == [30, 31], calls            # resumed at 30, not 0, not 500
    assert any("skipping checkpoint step_00000500" in l for l in logs), logs
    assert float(out.params["w"][0, 0]) == 1.0  # really loaded step-30 payload


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    state = _tiny_state()
    ckpt.save(str(tmp_path), 3, state)
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# Data pipelines
# ---------------------------------------------------------------------------

def test_lm_batch_deterministic():
    cfg = LMStreamConfig(vocab_size=1000, seq_len=32, global_batch=4, seed=9)
    a, b = lm_batch(cfg, 5), lm_batch(cfg, 5)
    assert np.array_equal(a["inputs"], b["inputs"])
    c = lm_batch(cfg, 6)
    assert not np.array_equal(a["inputs"], c["inputs"])
    assert a["inputs"].max() < 1000 and a["inputs"].min() >= 0


def test_dirichlet_partition_covers_and_skews():
    x, y, _, _ = make_image_dataset(ImageDataConfig(n_train=2000, n_test=10))
    parts = dirichlet_partition(y, n_workers=20, alpha=0.1, seed=0)
    stats = heterogeneity_stats(y, parts)
    assert stats["mean_label_entropy"] < 0.75 * stats["max_entropy"], "alpha=0.1 must skew"
    parts_iid = dirichlet_partition(y, n_workers=20, alpha=100.0, seed=0)
    stats_iid = heterogeneity_stats(y, parts_iid)
    assert stats_iid["mean_label_entropy"] > stats["mean_label_entropy"]


# ---------------------------------------------------------------------------
# Worker sampling
# ---------------------------------------------------------------------------

def test_participation_rate_and_determinism():
    rs = round_seed(123, 0)
    hits = [bool(participation_mask(rs, 0, w, 0.3)) for w in range(2000)]
    rate = np.mean(hits)
    assert abs(rate - 0.3) < 0.05
    hits2 = [bool(participation_mask(rs, 0, w, 0.3)) for w in range(2000)]
    assert hits == hits2
    assert bool(participation_mask(rs, 0, 5, 1.0)) is True


# ---------------------------------------------------------------------------
# Launcher: one executable per step config, compile-cache placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("server,vote_impl", [("scaled_sign_ef", "psum"),
                                              ("majority_vote", "allgather_packed")])
def test_launcher_state_placement_reuses_step0_executable(server, vote_impl):
    """The initial TrainState carries the placement the step returns, so the
    second step hits the first step's executable instead of recompiling."""
    from repro.launch import train

    args = train.build_parser().parse_args(
        ["--arch", "mamba2-370m", "--steps", "2", "--seq-len", "16",
         "--server", server, "--vote-impl", vote_impl])
    cfg, model, mesh, step, state, comp = train.build_everything(args)
    batch_fn = train.batch_fn_for(cfg, args)
    with jax.sharding.set_mesh(mesh):
        for i in range(2):
            state, metrics = step(state, batch_fn(i))
    assert np.isfinite(float(metrics["loss"]))
    assert step._cache_size() == 1


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_directory(env_dir, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR when set, else the fixed <checkout>/.jax_cache.
    Run in a child so this process's jax config stays untouched."""
    import pathlib
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    root = pathlib.Path(__file__).resolve().parents[1]
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(root / "src")
    want = root / ".jax_cache"
    if env_dir is not None:
        want = tmp_path / env_dir
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120).stdout.split()
    assert out == [str(want), str(want)]
